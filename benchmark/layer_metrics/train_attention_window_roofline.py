"""The window layers' attention as a share of its compute roofline: the
(query, key) pairs the causal window allows x 4 x head_dim x heads
(``flops_trinity.attention_forward_flops``) x 3 for forward and
backward, for every sliding layer and sample of a step, at the chip's
bf16 peak, over ``train_attention_window_device_ms``. The
rematerialised forward runs in that time and is not counted as work."""

from benchmark.lib import decoder_scopes, flops_trinity


def read(ctx):
    return decoder_scopes.attention_roofline(ctx, flops_trinity.SLIDING,
                                             window=True)
