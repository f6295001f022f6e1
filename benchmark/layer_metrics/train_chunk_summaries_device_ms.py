"""Device time per train step, forward and backward, under the scope
``eva_chunk_summaries``: the learned softmax-pool of every chunk of
keys and values into one summary each, and its gradients to k, v,
``adaptive_phi`` and ``adaptive_mu_k``. Nothing where no operation
carries the scope."""

from benchmark.lib import eva_scopes


def read(ctx):
    return eva_scopes.summaries_ms(ctx)
