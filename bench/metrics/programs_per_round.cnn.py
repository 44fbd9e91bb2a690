"""Device programs started per S2FL round in a CNN federation's traced
window: the first chip's XLA modules over the ``s2fl.round`` spans, both
started in the window; see ``_spans.py``."""
from metrics._spans import per_round


def read(ctx):
    return per_round(ctx, "samples")
