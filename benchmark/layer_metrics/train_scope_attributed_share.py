"""Share of the device's operation time that the names explain: the
operations that are collectives or whose ``op_name`` puts them in
forward, backward or optimizer (``benchmark/lib/scope_reduce.phase``).
100 minus this is ``other``, whose largest operations are printed to
standard error with the scope tables."""

from benchmark.lib import scope_reduce


def read(ctx):
    scopes = scope_reduce.for_cell(ctx)
    if not scopes:
        return None
    return 100.0 * scopes["attributed_share"]
