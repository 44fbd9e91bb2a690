"""Device idle share in a language model's traced window; see
``_idle.py``."""
from metrics._idle import idle


def read(ctx):
    return idle(ctx, "tokens")
