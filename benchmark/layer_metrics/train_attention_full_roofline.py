"""The full-attention layers' attention as a share of its compute
roofline: as ``train_attention_window_roofline``, over the causal pairs
of the ``full_attention`` layers and
``train_attention_full_device_ms``."""

from benchmark.lib import decoder_scopes


def read(ctx):
    return decoder_scopes.attention_roofline(ctx, "full_attention",
                                             window=False)
