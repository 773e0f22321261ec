"""Share of its roofline that the Pallas paged-decode kernel reaches over
the traced window (%): the least time its ideal work needs (the keys and
values of the positions each consuming lane attends to, read once; see
``counts.paged_decode_call``) over the summed device time of its events.
Its events are those whose ``pallas_call`` lies in
``kernels/attention/decode.py``.  Moves ``tok_per_s``."""

import roofline

SOURCE = "kernels/attention/decode.py"


def read(rec, peaks):
    work = rec.facts.get("kernel_work", {})
    if rec.trace is None or "paged_decode" not in work:
        return None
    return roofline.share(rec.trace, SOURCE, work["paged_decode"],
                          work["paged_decode_evals"], peaks)
