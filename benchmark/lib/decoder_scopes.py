"""What the sparse decoder's per-layer readers share: device time per
step under the attention paths of each kind and under the expert
layer's scopes, from ``scope_reduce``'s tables of the traced epoch.

The attention dispatcher names a window call ``attention_<path>_window``
and a full one ``attention_<path>``; ``DroplessExperts`` scopes its
parts ``moe_route`` (router, top-k, weights, counts), ``moe_dispatch``
(sort, gather), ``moe_experts`` (the grouped products), ``moe_combine``
(weights, sum per token) and ``moe_shared``. All times are forward +
backward, the rematerialised forward included (it runs on the chip)."""

from __future__ import annotations

from typing import Optional

from benchmark.lib import flops_trinity, scope_reduce
from benchmark.lib.peaks import peaks_for

MOE_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
              "moe_shared")
NOT_MATMUL = ("moe_route", "moe_dispatch", "moe_combine")


def attention_ms(ctx: dict, window: bool) -> Optional[float]:
    """Milliseconds a step under the attention paths of one kind;
    ``None`` where no operation carries such a scope."""
    scopes = scope_reduce.for_cell(ctx)
    if not scopes:
        return None
    mine = [ms for name, ms in scopes["attention_ms"].items()
            if name.endswith("_window") == window]
    return sum(mine) if mine else None


def moe_ms(ctx: dict, which=MOE_SCOPES) -> Optional[float]:
    """Milliseconds a step in operations whose module path holds one of
    the scopes ``which``; ``None`` where there is none."""
    scopes = scope_reduce.for_cell(ctx)
    if not scopes:
        return None
    mine = [row["total_ms"] for row in scopes["modules"]
            if set(row["scope"].split("/")) & set(which)]
    return sum(mine) if mine else None


def attention_roofline(ctx: dict, kind: str, window: bool) -> Optional[float]:
    """Percent: the layers of ``kind``'s allowed (query, key) pairs x 4
    x head_dim x heads x 3 (forward and backward; the rematerialised
    forward runs in the time and is not counted as work), for every
    sample of a step at the chip's bf16 peak, over :func:`attention_ms`."""
    ms = attention_ms(ctx, window=window)
    if not ms:
        return None
    config = ctx["config"]
    flops = (3 * flops_trinity.attention_forward_flops(
        config, ctx["cell"]["data"]["seq_len"], kind)
        * flops_trinity.layers_of(config, kind)
        * ctx["window"]["batch"] / ctx["chips"])
    least_s = flops / peaks_for(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * least_s / (ms / 1e3)
