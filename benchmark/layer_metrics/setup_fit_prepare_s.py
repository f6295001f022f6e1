"""The process's first ``fit_prepare`` span: the warm-up ``fit`` before
its first wait for a batch (``_ensure_built``, the optimizer's eager
``init``, ``_build_train_step``). ``None`` where the program's span ring
no longer holds its first span."""

from benchmark.lib import host_spans


def read(ctx):
    return host_spans.first_span_s("fit_prepare")
