"""The hybrid decoder's gated attention as a share of its compute
roofline: the full-attention layers' causal pairs x 4 x 256 x 16 heads
x 3 (forward and backward; a forward run again is in the time, not in
the work) at the chip's bf16 peak, over the time under every
``attention_<path>`` scope (the flash kernels at heads of 256, eight
query heads a KV head). Nothing where the configuration has no
linear-attention widths or the trace no such scope."""

from benchmark.lib import hybrid_scopes


def read(ctx):
    return hybrid_scopes.attention_roofline(ctx)
