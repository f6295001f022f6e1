"""Share of the traced window in which a collective operation
(all-reduce, all-gather, reduce-scatter, all-to-all,
collective-permute) was in flight on a device, mean over the chips."""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    return 100.0 * trace["collective_s"] / trace["window_s"]
