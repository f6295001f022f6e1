"""Device time per train step in operations under the program's
``optimizer`` scope (``Estimator._step_math``), mean over the chips
used. An update that XLA fused into a weight-gradient fusion runs under
that fusion's name and is not counted here. Nothing, with a line on
standard error, when no operation carries the scope: the program has
none, or a compile cache filled by an older tree served its names."""

import sys

from benchmark.lib import scope_reduce


def read(ctx):
    ms = scope_reduce.phase_ms(ctx, "optimizer")
    if ms == 0:
        print("train_optimizer_device_ms: no operation of the trace "
              "carries the scope 'optimizer' (a program without it, or an "
              "executable from a compile cache that an older tree filled)",
              file=sys.stderr)
        return None
    return ms
