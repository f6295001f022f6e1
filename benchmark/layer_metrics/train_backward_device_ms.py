"""Device time per train step in backward operations: those whose
``op_name`` says ``transpose(`` (``benchmark/lib/scope_reduce.phase``),
the forward operations rematerialised for the backward pass included,
gradient collectives not; mean over the chips used."""

from benchmark.lib import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "backward")
