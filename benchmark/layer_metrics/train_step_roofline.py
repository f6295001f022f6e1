"""The train step's share of its roofline: the least time one chip
could take for one step -- the larger of operations over peak FLOP/s
and bytes over peak bytes/s, both from ``benchmark/lib/flops.py`` --
over the device-busy time per step from the trace. The bound that
applies is printed to standard error."""

import sys

from benchmark.lib.peaks import peaks_for


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    peaks = peaks_for(ctx["device_kind"])
    per_chip_batch = ctx["window"]["batch"] / ctx["chips"]
    by_flops = (ctx["work"]["flops_per_sample"] * per_chip_batch
                / peaks["bf16_flops_per_s"])
    by_bytes = ctx["work"]["min_bytes_per_step"] / peaks["hbm_bytes_per_s"]
    busy = trace["busy_s"] / ctx["window"]["steps_per_epoch"]
    print(f"train_step_roofline: least {max(by_flops, by_bytes):.6f} s/step "
          f"(flops {by_flops:.6f}, bytes {by_bytes:.6f}; bound by "
          f"{'flops' if by_flops >= by_bytes else 'bytes'}), "
          f"busy {busy:.6f} s/step", file=sys.stderr)
    return 100.0 * max(by_flops, by_bytes) / busy
