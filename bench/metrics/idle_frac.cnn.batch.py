"""Idle share of a CNN federation's traced window under the program's
``PARTS["batch"]`` spans; see ``_spans.py``."""
from metrics._spans import idle_in


def read(ctx):
    return idle_in(ctx, "batch")
