"""The process's first ``train_step`` span: step 0 of the warm-up
``fit``, which traces, lowers and compiles the step program (or reads
it from the cache) and dispatches its first run. ``None`` where the
program's span ring no longer holds its first span."""

from benchmark.lib import host_spans


def read(ctx):
    return host_spans.first_span_s("train_step")
