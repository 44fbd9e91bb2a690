"""Idle share of the device in the traced window: one minus the union of
device-operation intervals over the window (``trace_reduce``), averaged
over the chips."""


def idle(ctx, unit):
    tr = ctx["trace"]
    if tr is None or ctx["unit"] != unit:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
