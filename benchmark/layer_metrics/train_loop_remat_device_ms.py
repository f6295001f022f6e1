"""Device time per train step of the looped decoder's second forward:
every operation whose module path holds ``rematted_computation``, what
the layers' ``nn.remat`` computes again for the backward pass under the
policy the model chose for 32 layer applications. A part of
``train_backward_device_ms``. Nothing where no operation carries it."""

from benchmark.lib import loop_scopes


def read(ctx):
    return loop_scopes.remat_ms(ctx)
