"""Device time per train step, forward and backward, under the calls of
``ops.attention.eva_attention`` (scopes ``attention_<path>_eva``): the
in-window causal kernels, the calls over the chunk summaries and what
joins them. Nothing where no operation carries such a scope."""

from benchmark.lib import eva_scopes


def read(ctx):
    return eva_scopes.kernel_ms(ctx)
