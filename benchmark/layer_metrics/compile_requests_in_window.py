"""XLA compile requests (backend compiles and cache reads alike) between
the window's first and last operation, from ``jax.monitoring``. Must
read 0: a compile inside the window is a stall of seconds."""


def read(ctx):
    return ctx["compiles_in_window"]["compiles"]
