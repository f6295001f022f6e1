"""The held experts' load over the window against a balanced router's:
held assignments (the program's ``zoo_model_moe_assignments_held_total``
over every expert layer) over layers x tokens x experts per token x
held / routed-over (10 x 32 / 512 at the published widths). 1 is what
a deployment's balanced router gives; above it the grouped products
and the bounded passes work more rows. Nothing where the program counts
no held assignment."""

from benchmark.lib import hybrid_scopes


def read(ctx):
    return hybrid_scopes.held_load_over_expected(ctx)
