"""Assignments the expert layers left out over the window (the
program's ``zoo_model_moe_assignments_dropped_total``). Must read 0: the
layer is dropless, and the runner's ``correct`` says so too."""


def read(ctx):
    dropped = (ctx.get("moe") or {}).get("moe_assignments_dropped")
    if not dropped:
        return None
    return int(sum(sum(v) for v in dropped.values()))
