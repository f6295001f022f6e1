"""Device time per train step, forward and backward, under the calls of
``ops.attention.dot_product_attention`` whose values' width is not the
queries' (scopes ``attention_<path>_latent``): latent attention's
kernels. Nothing where no operation carries such a scope."""

from benchmark.lib import latent_scopes


def read(ctx):
    return latent_scopes.kernel_ms(ctx)
