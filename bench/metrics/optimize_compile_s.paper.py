"""Seconds of the ``optimize()`` call spent segmenting, collapsing and
generating the stacks' code and executors (the ``optimize.compile`` span,
from the program's span registry).  Moves ``setup_s``."""

import spans


def read(rec, peaks):
    return spans.registry_seconds("optimize.compile")
