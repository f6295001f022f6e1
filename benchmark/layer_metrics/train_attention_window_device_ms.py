"""Device time per train step, forward and backward, under the window
calls of ``ops.attention.dot_product_attention`` (scopes
``attention_<path>_window``): the sliding layers' attention. Nothing
where no operation carries such a scope."""

from benchmark.lib import decoder_scopes


def read(ctx):
    return decoder_scopes.attention_ms(ctx, window=True)
