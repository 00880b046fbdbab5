"""Share of its roofline that the raw read kernel (``noisy_read``, one per
iterative bound-management trial) reaches in the traced training window,
in %."""

from benchlib import roofline


def read(readings):
    return roofline.kernel_share(readings, "noisy_read")
