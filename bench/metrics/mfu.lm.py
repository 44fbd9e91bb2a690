"""MFU of a language model's training steps (unit: tokens); see
``_mfu.py``."""
from metrics._mfu import mfu


def read(ctx):
    return mfu(ctx, "tokens")
