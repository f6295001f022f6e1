"""Device idle per step, between step programs, while the next one was
not yet dispatched or not yet started: the caller's thread was in
``train_step`` (dispatch), ``log_sync`` or the loop's own Python
(``fit_loop``), or the dispatched program had not started
(``launch``)."""

from benchmark.lib import host_spans


def read(ctx):
    result = host_spans.for_cell(ctx)
    if not result:
        return None
    return host_spans.idle_ms(ctx, host_spans.HOST_LATE) / result["steps"]
