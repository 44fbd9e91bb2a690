"""MFU of a CNN federation's rounds (unit: samples); see ``_mfu.py``."""
from metrics._mfu import mfu


def read(ctx):
    return mfu(ctx, "samples")
