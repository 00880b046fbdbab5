"""Whole train step's share of the chip's peak: model FLOPs per sample (6 x
the multiply-adds of the tiles' forward, backward and update, from layer
shapes) x samples per second of the window, over the peak, in %."""

from benchlib import roofline


def read(readings):
    if "samples_per_s" not in readings:
        return None
    pk = roofline.peaks(readings["device"]["kind"])
    return (100.0 * readings["samples_per_s"]
            * readings["model_flops_per_sample"] / pk["flops_per_s"])
