"""The held experts' grouped matmuls' share of their roofline, in
percent: the least time the chip could take for every call the window's
steps made (per call, the larger of its FLOPs over the bf16 peak and its
HBM bytes over the HBM bandwidth, at the expected routed rows; the calls
of a step as the path published them from ``flops/<family>``) over the
calls' measured seconds; see ``_gmm.py``."""
from metrics._gmm import calls, published


def read(ctx):
    found, step = calls(ctx), published()
    if found is None or step is None:
        return None
    peaks = ctx["peaks"]
    per_step = sum(n * max(ops / peaks["bf16_flops"],
                           moved / peaks["hbm_bytes_per_s"])
                   for n, ops, moved in step["calls"])
    least = per_step * ctx["work"] / step["work"]
    return 100.0 * least / sum(secs for secs, _ in found)
