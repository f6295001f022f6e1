"""Device time per train step under the attention dispatcher's
``attention_<path>`` scopes in the looped decoder's cell: every layer
application's call, forward and backward and whatever the
rematerialised forward runs again. Nothing where no operation carries
such a scope."""

from benchmark.lib import loop_scopes


def read(ctx):
    return loop_scopes.attention_ms(ctx)
