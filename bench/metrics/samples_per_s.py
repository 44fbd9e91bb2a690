"""Trained samples of every call completed in the window, over the
window's seconds."""


def read(ctx):
    if ctx["unit"] != "samples":
        return None
    return ctx["work"] / ctx["window_s"]
