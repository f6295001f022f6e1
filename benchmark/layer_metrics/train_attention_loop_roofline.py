"""The looped decoder's attention calls as a share of their compute
roofline: causal pairs x 4 x head_dim x heads x 3 (forward and
backward; a forward run again for the backward is in the time and not
in the work) x layers x total_ut_steps at the chip's bf16 peak, over
``train_attention_loop_device_ms``. Nothing where the configuration has
no ``total_ut_steps`` or the trace no such scope."""

from benchmark.lib import loop_scopes


def read(ctx):
    return loop_scopes.attention_roofline(ctx)
