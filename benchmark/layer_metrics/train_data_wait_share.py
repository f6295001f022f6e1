"""Share of the window that the fit loop spent waiting for its next
batch: the program's own ``fit(profile=True)`` ``data_wait`` stage
seconds over the window's seconds."""


def read(ctx):
    return 100.0 * ctx["data_wait_s"] / ctx["window"]["seconds"]
