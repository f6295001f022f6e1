"""Device time per train step, forward and backward, under the calls of
``ops.attention.dot_product_attention`` without a window (scopes
``attention_<path>``): the full-attention layers. Nothing where no
operation carries such a scope."""

from benchmark.lib import decoder_scopes


def read(ctx):
    return decoder_scopes.attention_ms(ctx, window=False)
