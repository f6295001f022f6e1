"""What the byte decoder's per-layer readers share: device time per
step under the dispatcher's ``attention_<path>_eva`` scope (EVA's joint
softmax over a window's token keys and the earlier windows' chunk
summaries, whichever kernels it is made of) and under the pooling's
``eva_chunk_summaries`` scope, from ``scope_reduce``'s tables of the
traced epoch. All times are forward + backward, whatever the
rematerialised forward runs again included (it runs on the chip)."""

from __future__ import annotations

from typing import Optional

from benchmark.lib import scope_reduce

PAIRS_GAUGE = "zoo_model_attention_eva_pairs_computed_ratio"
SUMMARIES_SCOPE = "eva_chunk_summaries"


def kernel_ms(ctx: dict) -> Optional[float]:
    """Milliseconds a step under an EVA attention call; ``None`` where
    no operation carries such a scope."""
    scopes = scope_reduce.for_cell(ctx)
    if not scopes:
        return None
    mine = [ms for name, ms in scopes["attention_ms"].items()
            if name.endswith("_eva")]
    return sum(mine) if mine else None


def summaries_ms(ctx: dict) -> Optional[float]:
    """Milliseconds a step in operations whose module path holds the
    pooling's scope; ``None`` where there is none."""
    scopes = scope_reduce.for_cell(ctx)
    if not scopes:
        return None
    mine = [row["total_ms"] for row in scopes["modules"]
            if SUMMARIES_SCOPE in row["scope"].split("/")]
    return sum(mine) if mine else None
