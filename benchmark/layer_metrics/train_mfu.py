"""Model FLOP/s utilisation, end to end: the operations the forward and
backward passes need per sample (``benchmark/lib/flops.py``, from the
published widths) times samples per second on the host's clock (the
runner's median over epochs, as ``train_samples_per_s``), over chips
times the published bf16 peak. It counts idle time against the
program; it is not a kernel's roofline share."""

from benchmark.lib.peaks import peaks_for


def read(ctx):
    if ctx["rehearsal"]:
        return None
    w = ctx["window"]
    peak = peaks_for(ctx["device_kind"])["bf16_flops_per_s"] * ctx["chips"]
    return (100.0 * ctx["work"]["flops_per_sample"] * w["samples_per_s"]
            / peak)
