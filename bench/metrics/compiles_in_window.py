"""Backend compilations inside the window, counted from JAX's own
``backend_compile_duration`` events (JAX emits one for a load from the
persistent cache too): a program met for the first time there, and not
warmed in set-up."""


def read(ctx):
    return ctx["compiles_in_window"]
