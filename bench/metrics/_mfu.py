"""Model FLOP utilization, in percent: the model FLOPs the trained work
needs (from its shapes, ``bench/flops``) over the window's seconds and the
chips' bf16 peak."""


def mfu(ctx, unit):
    if ctx["unit"] != unit:
        return None
    flops = ctx["work"] * ctx["flops_per_unit"]
    peak = ctx["peaks"]["bf16_flops"] * ctx["n_devices"]
    return 100.0 * flops / ctx["window_s"] / peak
