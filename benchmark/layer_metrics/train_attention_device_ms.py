"""Device time per train step, forward and backward, in operations under
one of ``ops.attention.dot_product_attention``'s scopes
(``attention_flash``, ``attention_stock_pallas``, ``attention_einsum``,
``attention_reference``), mean over the chips used. Which path ran, and
for how long, is printed to standard error. Nothing when no operation
carries such a scope."""

import sys

from benchmark.lib import scope_reduce


def read(ctx):
    scopes = scope_reduce.for_cell(ctx)
    if not scopes or not scopes["attention_ms"]:
        return None
    print("train_attention_device_ms: " + ", ".join(
        f"{name} {ms:.3f} ms" for name, ms in
        sorted(scopes["attention_ms"].items())), file=sys.stderr)
    return sum(scopes["attention_ms"].values())
