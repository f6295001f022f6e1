"""Score entries the EVA attention call's blocks compute, forward and
backward, over the pairs its mask allows: the gauge
``zoo_model_attention_eva_pairs_computed_ratio`` the attention modules
publish while the step is traced (a function of the shapes and the
path; every layer reads the same, the largest is reported). What block
size and the ragged summary run waste. Nothing where the runner hands
no gauges or the model publishes none."""

from benchmark.lib import eva_scopes


def read(ctx):
    by_module = (ctx.get("gauges") or {}).get(eva_scopes.PAIRS_GAUGE)
    return max(by_module.values()) if by_module else None
