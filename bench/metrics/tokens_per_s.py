"""Trained tokens of every step completed in the window, over the
window's seconds."""


def read(ctx):
    if ctx["unit"] != "tokens":
        return None
    return ctx["work"] / ctx["window_s"]
