"""Seconds of the ``repro.api.optimize(...)`` call (trace, plan, verify,
code generation), by the host clock.  Moves ``setup_s``."""


def read(rec, peaks):
    return rec.facts.get("optimize_s")
