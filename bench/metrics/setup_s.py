"""Set-up seconds: from the start of the cell's build to the first call
of the window (weights, inputs, compilation or cache loads, warm-up)."""


def read(ctx):
    return ctx["setup_s"]
