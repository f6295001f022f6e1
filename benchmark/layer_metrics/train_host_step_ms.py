"""The caller's thread per step outside ``data_wait`` (the dispatch, the
log sync, the loop's own Python), median over the traced ``fit`` call:
the host's cost of a step, to set against ``train_step_device_ms``."""

from benchmark.lib import host_spans


def read(ctx):
    result = host_spans.for_cell(ctx)
    return result["host_step_ms"] if result else None
