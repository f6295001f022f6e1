"""Device time per train step under the scope ``delta_rule``: the
Gated DeltaNet layers' chunked delta rule (``ops/delta_rule.py``), its
chunk-parallel part and its scan over the chunks, forward, the forward
again for the backward, and the backward. Nothing where no operation
carries the scope."""

from benchmark.lib import hybrid_scopes


def read(ctx):
    return hybrid_scopes.delta_rule_ms(ctx)
