"""Seconds of the ``optimize()`` call spent in ``core/trace.py``'s
numerical probes (the ``trace.probe`` spans, from the program's span
registry).  Moves ``setup_s``."""

import spans


def read(rec, peaks):
    return spans.registry_seconds("trace.probe")
