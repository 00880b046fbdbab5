"""Share of its roofline that the coincidence-count kernel
(``pulse_counts``) reaches in the traced training window, in %."""

from benchlib import roofline


def read(readings):
    return roofline.kernel_share(readings, "pulse_counts")
