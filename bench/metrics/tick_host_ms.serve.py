"""Host time per scheduler tick in which the serve engine keeps the
device waiting (ms), over the traced window: the median, over the ticks
wholly inside it, of the ``engine.tick`` span less its ``engine.sync``
span, plus the ``engine.admit`` span before it (``spans.tick_host_ms``).
Moves ``tok_per_s``."""

import spans


def read(rec, peaks):
    return spans.tick_host_ms(rec.trace)
