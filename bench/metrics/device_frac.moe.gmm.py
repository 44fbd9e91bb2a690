"""Share of a language model's busy device time in the held experts'
grouped matmuls; see ``_gmm.py``."""
from metrics import _gmm

# the one reader that asks trace_reduce for the calls
KERNEL = _gmm.KERNEL


def read(ctx):
    found = _gmm.calls(ctx)
    if found is None:
        return None
    return sum(secs for secs, _ in found) / ctx["n_devices"] / ctx[
        "trace"]["busy_s"]
