"""Latent attention's kernels as a share of their compute roofline:
the causal pairs x 2 x (nope + rot + v) x heads x 3 (forward and
backward; a forward run again for the backward is in the time and not
in the work) x layers, for every sample of a step at the chip's bf16
peak, over ``train_attention_latent_device_ms``. The work is the
published one (``flops_moonlight.attention_forward_flops``), whichever
form of the kernel runs and whatever it pads. Nothing where the
configuration has no latent widths or the trace no such scope."""

from benchmark.lib import flops_moonlight, latent_scopes
from benchmark.lib.peaks import peaks_for


def read(ctx):
    config = ctx["config"]
    if "kv_lora_rank" not in config:
        return None
    ms = latent_scopes.kernel_ms(ctx)
    if not ms:
        return None
    flops = (3 * flops_moonlight.attention_forward_flops(
        config, ctx["cell"]["data"]["seq_len"])
        * config["num_hidden_layers"] * ctx["window"]["batch"]
        / ctx["chips"])
    least_s = flops / peaks_for(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * least_s / (ms / 1e3)
