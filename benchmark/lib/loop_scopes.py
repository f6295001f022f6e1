"""What the looped decoder's per-layer readers share: device time per
step, from ``scope_reduce``'s tables of the traced epoch, under the
program's scopes ``loop_head`` (every pass's head a row block at a time
and the exit gates) and ``exit_loss`` (the labels' logits and the
exit-weighted loss), under the attention dispatcher's
``attention_<path>`` scopes, and in the forward operations that run a
second time for the backward pass (``rematted_computation``). All
phases unless said otherwise. Also the program's ``zoo_model_loop_*``
counters. Every function gives ``None`` where the program has no such
scope or counter."""

from __future__ import annotations

from typing import Optional

from benchmark.lib import decoder_scopes, flops_ouro, scope_reduce
from benchmark.lib.peaks import peaks_for

HEAD_SCOPES = ("loop_head", "exit_loss")
REMAT = "rematted_computation"
EXIT_PROBABILITY = "zoo_model_loop_exit_probability_thousandths_total"
STEPS = "zoo_model_loop_steps_total"


def head_ms(ctx: dict) -> Optional[float]:
    return decoder_scopes.moe_ms(ctx, which=HEAD_SCOPES)


def remat_ms(ctx: dict) -> Optional[float]:
    return decoder_scopes.moe_ms(ctx, which=(REMAT,))


def attention_ms(ctx: dict) -> Optional[float]:
    scopes = scope_reduce.for_cell(ctx)
    if not scopes or not scopes["attention_ms"]:
        return None
    return sum(scopes["attention_ms"].values())


def _share_of_peak(ctx: dict, flops_of, ms: Optional[float]):
    """Percent: ``flops_of(config, seq)`` for every sample of a step at
    the chip's bf16 peak, over ``ms``."""
    if not ms or "total_ut_steps" not in ctx["config"]:
        return None
    flops = (flops_of(ctx["config"], ctx["cell"]["data"]["seq_len"])
             * ctx["window"]["batch"] / ctx["chips"])
    least_s = flops / peaks_for(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * least_s / (ms / 1e3)


def head_roofline(ctx: dict) -> Optional[float]:
    return _share_of_peak(ctx, flops_ouro.heads_train_flops, head_ms(ctx))


def attention_roofline(ctx: dict) -> Optional[float]:
    return _share_of_peak(ctx, flops_ouro.attention_train_flops,
                          attention_ms(ctx))


def exit_expected_steps(registry_snapshot: dict) -> Optional[float]:
    """``sum_t t x (mean exit probability of pass t)`` over the steps
    the program has counted, from the growth of its counters since the
    process began (published at each epoch's sync)."""
    def values(name):
        """{index: growth} of one published family, summed over its
        ``module`` labels (a scalar's index is 0)."""
        out = {}
        family = registry_snapshot.get(name) or {"values": {}}
        for labels, value in family["values"].items():
            pairs = dict(p.split("=", 1) for p in labels.split(","))
            index = int(pairs.get("index", 0))
            out[index] = out.get(index, 0.0) + value
        return out

    steps = sum(values(STEPS).values())
    exits = values(EXIT_PROBABILITY)
    if not steps or not exits:
        return None
    return sum((t + 1) * total / steps / 1e3 for t, total in exits.items())
