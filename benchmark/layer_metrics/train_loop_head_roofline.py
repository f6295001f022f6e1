"""The looped decoder's heads as a share of their compute roofline:
3 x total_ut_steps x 2 x hidden x vocabulary x sequence (each pass's
logits and their cotangent's two products; the logits computed again
for the backward pass are in the time and not in the work) at the
chip's bf16 peak, over ``train_loop_head_device_ms``. While the blocked
head is XLA's this bounds it; once it is a kernel it is the kernel's
share. Nothing where the configuration has no ``total_ut_steps`` or the
trace no such scope."""

from benchmark.lib import loop_scopes


def read(ctx):
    return loop_scopes.head_roofline(ctx)
