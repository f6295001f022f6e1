"""The delta rule's share of its roofline: 3 x its chunked form's
forward operations and its least bytes forward and backward
(``flops_qwen3_next.delta_rule_forward_flops`` /
``delta_rule_train_min_bytes``: q, k, v, g, beta in and o out once,
the gradients out and the chunk states the backward reads), at the
larger of the two bounds, over ``train_delta_rule_device_ms``. Nothing
where the configuration has no linear-attention widths or the trace no
such scope."""

from benchmark.lib import hybrid_scopes


def read(ctx):
    return hybrid_scopes.delta_rule_roofline(ctx)
