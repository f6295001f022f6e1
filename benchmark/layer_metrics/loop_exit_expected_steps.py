"""The pass at which a token leaves the looped decoder, in expectation:
``sum_t t x mean p_t`` from the program's
``zoo_model_loop_exit_probability_thousandths_total`` and
``zoo_model_loop_steps_total`` counters (device-side, published at each
epoch's sync), over every step the process has counted. Nothing where
the program publishes no such counter."""

from benchmark.lib import loop_scopes


def read(ctx):
    from analytics_zoo_tpu.obs.metrics import get_registry

    return loop_scopes.exit_expected_steps(get_registry().snapshot())
