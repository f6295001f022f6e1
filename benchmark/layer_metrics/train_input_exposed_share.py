"""Device idle time put down to a wait for a batch (``first_batch`` +
``data_wait``: the caller sat in ``next(batches)`` while the device had
nothing queued), over the traced window: the part of the data wait that
was NOT hidden behind queued steps, where ``train_data_wait_share``
counts hidden and exposed waits alike."""

from benchmark.lib import host_spans


def read(ctx):
    result = host_spans.for_cell(ctx)
    if not result:
        return None
    return 100.0 * 1e6 * host_spans.idle_ms(ctx, host_spans.INPUT) \
        / result["window_ns"]
