"""EVA attention's calls as a share of their compute roofline: the
pairs its mask allows (in-window and query x summary) x 4 x head_dim x
heads x 3 (forward and backward; a forward run again for the backward
is in the time and not in the work) x layers, for every sample of a
step at the chip's bf16 peak, over ``train_attention_eva_device_ms``.
The work is the published one
(``flops_evabyte.attention_forward_flops``), whichever form of the
kernel runs and whatever its blocks pad. Nothing where the
configuration has no window and chunk or the trace no such scope."""

from benchmark.lib import eva_scopes, flops_evabyte
from benchmark.lib.peaks import peaks_for


def read(ctx):
    config = ctx["config"]
    if "chunk_size" not in config or "window_size" not in config:
        return None
    ms = eva_scopes.kernel_ms(ctx)
    if not ms:
        return None
    flops = (3 * flops_evabyte.attention_forward_flops(
        config, ctx["cell"]["data"]["seq_len"])
        * config["num_hidden_layers"] * ctx["window"]["batch"]
        / ctx["chips"])
    least_s = flops / peaks_for(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * least_s / (ms / 1e3)
