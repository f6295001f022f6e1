"""1 - busy union over the traced window, on the device that was busy
least. The window is one whole ``fit`` call of one epoch on the host's
clock, so the epoch's start and its drain count as idle."""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    return 100.0 * trace["idle_share_worst"]
