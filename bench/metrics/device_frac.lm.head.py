"""Share of a language model's busy device time in operations of the
``head`` scope; see ``_spans.py``."""
from metrics._spans import device_share


def read(ctx):
    return device_share(ctx, "head")
