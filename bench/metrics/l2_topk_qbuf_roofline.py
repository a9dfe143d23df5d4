"""l2_topk_qbuf_roofline (%): least time of the work the f32 scan had to do
(``work.py``) over the ``l2_topk_qbuf`` kernel's device time."""
from lirabench.series import kernel_roofline


def read(run):
    return kernel_roofline(run, "l2_topk_qbuf")
