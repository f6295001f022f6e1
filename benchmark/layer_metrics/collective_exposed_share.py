"""Share of the traced window in which a collective was in flight and
no other operation ran on that device: what overlap could still win."""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
