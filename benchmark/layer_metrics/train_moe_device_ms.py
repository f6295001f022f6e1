"""Device time per train step, forward and backward, in the expert
layers: every operation under ``moe_route``, ``moe_dispatch``,
``moe_experts``, ``moe_combine`` or ``moe_shared``
(``keras/layers/moe.DroplessExperts``)."""

from benchmark.lib import decoder_scopes


def read(ctx):
    return decoder_scopes.moe_ms(ctx)
