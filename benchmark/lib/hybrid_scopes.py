"""What the hybrid decoder's per-layer readers share (Qwen3-Next,
``models/text/hybrid_decoder_lm.py``): device time per step, from
``scope_reduce``'s tables of the traced epoch, in the Gated DeltaNet
mixers (module ``linear_mixer``: projections, the scopes
``short_conv``, ``delta_gates``, ``delta_rule`` and ``gated_norm``)
and under the attention dispatcher's ``attention_<path>`` scopes, as
shares of their rooflines; and the held experts' load over the window
from the program's ``zoo_model_moe_*`` counters. All phases, the
rematerialised forward included (it runs on the chip). Every function
gives ``None`` where the program has no such scope or counter, or the
configuration no linear-attention widths."""

from __future__ import annotations

import sys
from typing import Optional

from benchmark.lib import decoder_scopes, flops_qwen3_next, scope_reduce
from benchmark.lib.peaks import peaks_for

LINEAR_MIXER = "linear_mixer"
DELTA_RULE = "delta_rule"


def _hybrid(ctx: dict) -> bool:
    return "linear_num_value_heads" in ctx["config"]


def delta_rule_ms(ctx: dict) -> Optional[float]:
    return decoder_scopes.moe_ms(ctx, which=(DELTA_RULE,))


def linear_mixer_ms(ctx: dict) -> Optional[float]:
    return decoder_scopes.moe_ms(ctx, which=(LINEAR_MIXER,))


def _least_s(ctx: dict, flops: float, min_bytes: float = 0.0) -> float:
    """The least time a step's work of ``flops`` operations and
    ``min_bytes`` bytes (per sample) takes on one chip at its peaks."""
    per_chip = ctx["window"]["batch"] / ctx["chips"]
    peaks = peaks_for(ctx["device_kind"])
    return max(flops * per_chip / peaks["bf16_flops_per_s"],
               min_bytes * per_chip / peaks["hbm_bytes_per_s"])


def delta_rule_roofline(ctx: dict) -> Optional[float]:
    """Percent: the Gated DeltaNet layers' delta rule, forward and
    backward (3 x its forward operations; its least bytes, forward and
    backward), at the larger of the two bounds, over
    :func:`delta_rule_ms`. Prints which bound applies."""
    ms = delta_rule_ms(ctx)
    if not ms or not _hybrid(ctx):
        return None
    config, seq = ctx["config"], ctx["cell"]["data"]["seq_len"]
    layers = flops_qwen3_next.layer_counts(config)[0]
    flops = 3 * layers * flops_qwen3_next.delta_rule_forward_flops(
        config, seq)
    least = _least_s(ctx, flops, layers
                     * flops_qwen3_next.delta_rule_train_min_bytes(
                         config, seq))
    bound = "compute" if least == _least_s(ctx, flops) else "bandwidth"
    print(f"train_delta_rule_roofline: {bound}-bound, least "
          f"{1e3 * least:.3f} ms of {ms:.3f}", file=sys.stderr)
    return 100.0 * least / (ms / 1e3)


def attention_roofline(ctx: dict) -> Optional[float]:
    """Percent: the gated attention layers' causal pairs x 4 x head_dim
    x heads x 3, at the chip's bf16 peak, over every
    ``attention_<path>`` scope's time."""
    scopes = scope_reduce.for_cell(ctx)
    if not scopes or not scopes["attention_ms"] or not _hybrid(ctx):
        return None
    config = ctx["config"]
    flops = (3 * flops_qwen3_next.layer_counts(config)[1]
             * flops_qwen3_next.attention_forward_flops(
                 config, ctx["cell"]["data"]["seq_len"]))
    return 100.0 * _least_s(ctx, flops) / (
        sum(scopes["attention_ms"].values()) / 1e3)


def held_load_over_expected(ctx: dict) -> Optional[float]:
    """Held assignments over the window, all expert layers, over what a
    balanced router gives them: tokens x ``num_experts_per_tok`` x
    held / routed-over, a layer."""
    held = (ctx.get("moe") or {}).get("moe_assignments_held") or {}
    if not held or not _hybrid(ctx):
        return None
    config = ctx["config"]
    tokens = ctx["window"]["samples"] * ctx["cell"]["data"]["seq_len"]
    expected = (len(held) * tokens
                * flops_qwen3_next.held_per_token(config))
    return sum(sum(v) for v in held.values()) / expected
