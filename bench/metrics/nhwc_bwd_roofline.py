"""Share of its roofline that the generated NHWC backward kernels reach
over the traced window (%): the least time of their ideal work (see
``counts.nhwc_pool_stack_bwd``) over the summed device time of the events
whose ``pallas_call`` lies in ``kernels/fused_stack/nhwc_bwd.py``.  Moves
``img_per_s``."""

import roofline

SOURCE = "kernels/fused_stack/nhwc_bwd.py"


def read(rec, peaks):
    return roofline.per_step_share(rec, "nhwc_bwd", SOURCE, peaks)
