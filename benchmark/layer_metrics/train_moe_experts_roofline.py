"""The grouped expert products as a share of their roofline. Least
time: the larger of the COUNTED held assignments a step (the program's
``zoo_model_moe_assignments_held_total`` over the window's steps) x one
expert's parameters x 2 x 3 at the bf16 peak, and the held experts'
bfloat16 weights read three times (forward, and twice backward) at the
HBM peak; over the time under ``moe_experts``. The bound that applies
is printed to standard error."""

import sys

from benchmark.lib import decoder_scopes, flops_trinity
from benchmark.lib.peaks import peaks_for


def read(ctx):
    ms = decoder_scopes.moe_ms(ctx, ("moe_experts",))
    moe = ctx.get("moe")
    if not ms or not moe or not moe.get("moe_assignments_held"):
        return None
    config, peaks = ctx["config"], peaks_for(ctx["device_kind"])
    held_a_step = sum(sum(v) for v in moe[
        "moe_assignments_held"].values()) / ctx["window"]["steps"]
    by_flops = (held_a_step / ctx["chips"]
                * flops_trinity.expert_params(config) * 2 * 3
                / peaks["bf16_flops_per_s"])
    by_bytes = (3 * flops_trinity.held_expert_weight_bytes(config)
                / peaks["hbm_bytes_per_s"])
    print(f"train_moe_experts_roofline: least {max(by_flops, by_bytes):.6f}"
          f" s/step (flops {by_flops:.6f} at {held_a_step:.0f} held "
          f"assignments a step, bytes {by_bytes:.6f}), under moe_experts "
          f"{ms / 1e3:.6f} s/step", file=sys.stderr)
    return 100.0 * max(by_flops, by_bytes) / (ms / 1e3)
