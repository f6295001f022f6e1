"""Device time per train step in forward operations: those whose
``op_name`` says ``jvp(`` and neither ``transpose(`` nor ``/optimizer/``
(``benchmark/lib/scope_reduce.phase``), mean over the chips used."""

from benchmark.lib import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "forward")
