"""Device time per train step, forward and backward, in the latent
attention modules' operations outside the kernel's scope: the query,
down and up projections, the latent norm, RoPE and the joins, the
output projection (``latent_scopes.projections_ms``). Nothing where no
module is a latent attention."""

from benchmark.lib import latent_scopes


def read(ctx):
    return latent_scopes.projections_ms(ctx)
