"""Device time per model evaluation of the Mamba-2 mixers (ms), over the
traced window: the operations whose compiled metadata lies under the
program's ``mamba`` scope (projections, conv, state update, gated norm),
attributed as in ``moe_ms.hybrid`` (``scopes.py``).  Moves
``tok_per_s``."""

import scopes


def read(rec, peaks):
    return scopes.ms_per_evaluation(rec, "mamba")
