"""Device idle per ``fit`` call at the call's own edges: ``fit_prepare``
and step 0's dispatch before the first run, ``epoch_sync``,
``publish_counters`` and the return after the last (the first batch's
wait is the input path's: ``train_input_exposed_share``)."""

from benchmark.lib import host_spans


def read(ctx):
    return host_spans.idle_ms(ctx, host_spans.CALL_EDGES)
