"""Imbalance of the held experts' load over the window: the most
assignments any held expert of any layer took, over the mean of them
all (the program's ``zoo_model_moe_expert_assignments_total``). 1 is
perfect balance; the busiest expert's rows bound the grouped products'
tail."""


def read(ctx):
    per_expert = [n for values in (ctx.get("moe") or {}).get(
        "moe_expert_assignments", {}).values() for n in values]
    if not per_expert or not sum(per_expert):
        return None
    return max(per_expert) * len(per_expert) / sum(per_expert)
