"""Share of its roofline that the NHWC depth-first forward kernels reach
over the traced window (%): the least time of their ideal work (each
stack's input and parameters read once and its pooled output written
once, at the true channel count; see ``counts.nhwc_pool_stack_fwd``) over
the summed device time of the events whose ``pallas_call`` lies in
``kernels/fused_stack/nhwc.py``.  Moves ``img_per_s``."""

import roofline

SOURCE = "kernels/fused_stack/nhwc.py"


def read(rec, peaks):
    return roofline.per_step_share(rec, "nhwc_fwd", SOURCE, peaks)
