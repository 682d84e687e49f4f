"""Samples (images, or sequences of the configuration's length) trained per
second per chip: steps completed between the first and the last stamp of the
window, times the per-rank batch, over that time."""


def read(run):
    return run["window"]["samples_per_s_chip"]
