"""What the latent-attention decoder's per-layer readers share: device
time per step under the dispatcher's ``attention_<path>_latent`` scope
(a call whose values' width is not its queries') and in the attention
modules' operations outside it, from ``scope_reduce``'s tables of the
traced epoch. All times are forward + backward, whatever the
rematerialised forward runs again included (it runs on the chip)."""

from __future__ import annotations

from typing import Optional

from benchmark.lib import scope_reduce


def kernel_ms(ctx: dict) -> Optional[float]:
    """Milliseconds a step under a latent attention call; ``None``
    where no operation carries such a scope."""
    scopes = scope_reduce.for_cell(ctx)
    if not scopes:
        return None
    mine = [ms for name, ms in scopes["attention_ms"].items()
            if "_latent" in name]
    return sum(mine) if mine else None


def projections_ms(ctx: dict) -> Optional[float]:
    """Milliseconds a step in the operations of a module named
    ``attention`` that holds a ``latent_norm`` somewhere, outside every
    ``attention_<path>`` scope: the query, down and up projections, the
    latent norm, RoPE and the joins, the output projection. ``None``
    where no module is a latent attention."""
    scopes = scope_reduce.for_cell(ctx)
    if not scopes:
        return None
    paths = [row["scope"].split("/") for row in scopes["modules"]]
    if not any("latent_norm" in p and "attention" in p for p in paths):
        return None
    return sum(row["total_ms"] for row, p in zip(scopes["modules"], paths)
               if "attention" in p
               and not any(part.startswith("attention_") for part in p))
