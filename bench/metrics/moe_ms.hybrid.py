"""Device time per model evaluation of the expert layers (ms), over the
traced window: the operations whose compiled metadata lies under the
program's ``moe`` scope (router, dispatch, held experts, combine; the
shared expert has a scope of its own).  A fusion counts by its own
metadata, else its root's; a copy or prefetch without metadata counts
under the scope of the instruction that uses it (``scopes.py``).  Moves
``tok_per_s``."""

import scopes


def read(rec, peaks):
    return scopes.ms_per_evaluation(rec, "moe")
