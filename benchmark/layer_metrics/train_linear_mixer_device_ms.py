"""Device time per train step in the Gated DeltaNet mixers (module
``linear_mixer``): the projections in and out, the short convolution,
the gates, the delta rule and the gated norm, all phases. Nothing where
no module carries the name."""

from benchmark.lib import hybrid_scopes


def read(ctx):
    return hybrid_scopes.linear_mixer_ms(ctx)
