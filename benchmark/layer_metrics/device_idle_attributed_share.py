"""Share of the traced ``fit`` call's device idle time that has a cause
other than ``no_span``: what ``benchmark/lib/host_spans.py`` could put
down to a span of the program (or to the runtime, with the program's
dispatch already returned). The tracing's own coverage of idle time, as
``train_scope_attributed_share`` is of busy time."""

from benchmark.lib import host_spans


def read(ctx):
    result = host_spans.for_cell(ctx)
    if not result:
        return None
    return 100.0 * (1.0 - result["causes"][host_spans.NO_SPAN]
                    / result["idle_ns"])
