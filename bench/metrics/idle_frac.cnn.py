"""Device idle share in a CNN federation's traced window; see
``_idle.py``."""
from metrics._idle import idle


def read(ctx):
    return idle(ctx, "samples")
