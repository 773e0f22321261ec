"""Median inter-token gap of the measured window (ms): the steady
scheduler tick, host and device together.  Moves ``tok_per_s``."""


def read(rec, peaks):
    return rec.facts.get("itl_p50_ms")
