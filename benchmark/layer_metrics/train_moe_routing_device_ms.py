"""The part of ``train_moe_device_ms`` that is no expert's matmul:
``moe_route`` (router, top-k, weights, counts), ``moe_dispatch`` (sort,
gather) and ``moe_combine`` (weights, the sum per token)."""

from benchmark.lib import decoder_scopes


def read(ctx):
    return decoder_scopes.moe_ms(ctx, decoder_scopes.NOT_MATMUL)
