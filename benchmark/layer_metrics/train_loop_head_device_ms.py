"""Device time per train step under the looped decoder's scopes
``loop_head`` (every pass's head, a row block at a time, and the exit
gates) and ``exit_loss`` (the labels' logits and the exit-weighted
loss): forward, backward and the blocks' logits computed again.
Nothing where no operation carries such a scope."""

from benchmark.lib import loop_scopes


def read(ctx):
    return loop_scopes.head_ms(ctx)
