"""Idle share of a CNN federation's traced window under the program's
other ``s2fl.*`` spans: driver, scheduler, balance, channel; see
``_spans.py``."""
from metrics._spans import idle_elsewhere


def read(ctx):
    return idle_elsewhere(ctx)
