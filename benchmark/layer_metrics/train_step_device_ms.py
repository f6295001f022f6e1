"""Device-busy time per train step: the union of the intervals in which
an operation ran on the device, over the steps of the traced epoch
(mean over the chips used)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    return 1e3 * trace["busy_s"] / ctx["window"]["steps_per_epoch"]
