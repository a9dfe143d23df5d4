"""pq_adc_topk_qbuf_roofline (%): least time of the work the ADC shortlist
scan had to do (``work.py``) over the ``pq_adc_topk_qbuf`` kernel's device
time."""
from lirabench.series import kernel_roofline


def read(run):
    return kernel_roofline(run, "pq_adc_topk_qbuf")
