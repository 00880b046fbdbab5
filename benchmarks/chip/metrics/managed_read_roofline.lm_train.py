"""Share of its roofline that the fused managed read (``managed_read``: the
forward and transpose reads of every tile) reaches in the traced LM
training window, in %."""

from benchlib import roofline


def read(readings):
    return roofline.kernel_share(readings, "managed_read")
