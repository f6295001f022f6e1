"""Device time per train step of the forward operations that run a
second time for the backward pass: every operation whose module path
holds ``rematted_computation``, the name JAX gives what a
``jax.checkpoint`` (``nn.remat``) computes again
(``checkpoint/rematted_computation/layer_*/...``). A part of
``train_backward_device_ms``. Nothing where no operation carries it:
a model without ``nn.remat``."""

from benchmark.lib import decoder_scopes


def read(ctx):
    return decoder_scopes.moe_ms(ctx, which=("rematted_computation",))
