"""Tests for common runtime: config, context, triggers, timers."""

import os

import jax
import pytest

from analytics_zoo_tpu.common import config as config_mod
from analytics_zoo_tpu.common.config import ZooConfig
from analytics_zoo_tpu.common.context import ZooContext, init_zoo_context, stop_orca_context
from analytics_zoo_tpu.common.log import Timer
from analytics_zoo_tpu.common.triggers import (
    And,
    EveryEpoch,
    MaxEpoch,
    MaxIteration,
    MaxScore,
    MinLoss,
    Or,
    SeveralIteration,
    TriggerState,
)


class TestConfig:
    def test_defaults(self):
        conf = ZooConfig(conf_file="")
        assert conf.get("zoo.train.failure.retry_times") == 5
        assert conf.get("nonexistent", 42) == 42

    def test_layering_env_over_file_over_default(self, tmp_path, monkeypatch):
        f = tmp_path / "azt.conf"
        f.write_text("zoo.train.log_every_n_steps 7\nzoo.serving.batch_size 16\n")
        conf = ZooConfig(conf_file=str(f))
        assert conf.get("zoo.train.log_every_n_steps") == 7
        monkeypatch.setenv("AZT_ZOO_TRAIN_LOG_EVERY_N_STEPS", "99")
        assert conf.get("zoo.train.log_every_n_steps") == 99
        conf.set("zoo.train.log_every_n_steps", 3)
        assert conf.get("zoo.train.log_every_n_steps") == 3
        conf.unset("zoo.train.log_every_n_steps")
        assert conf.get("zoo.train.log_every_n_steps") == 99

    def test_coercion(self, monkeypatch):
        monkeypatch.setenv("AZT_ZOO_DATA_CHECK_BATCH_DIVISIBLE", "false")
        conf = ZooConfig(conf_file="")
        assert conf.get("zoo.data.check_batch_divisible") is False

    @pytest.mark.parametrize("key", [
        "zoo.ops.attention_impl", "zoo.ops.attention_flash_min_seq",
        "zoo.models.bn_stat_rows", "zoo.train.donate_buffers",
        "zoo.train.prng_impl"])
    def test_no_key_turns_the_compiled_step(self, key):
        """The train step is a function of the model, the shapes and
        the platform (PR 29): these five were read while it was traced,
        so setting one after the first ``fit`` silently did nothing."""
        assert key not in config_mod._DEFAULTS
        assert key not in config_mod._SPECS
        docs = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "runtime.md")
        with open(docs) as f:
            assert key not in f.read()


class TestContext:
    def test_init_default_mesh(self):
        stop_orca_context()
        ctx = init_zoo_context()
        try:
            assert ctx.num_devices == 8
            assert ctx.mesh.axis_names == ("data",)
            # idempotent
            assert init_zoo_context() is ctx
        finally:
            stop_orca_context()
        assert ZooContext.get() is None

    def test_custom_mesh_shape(self):
        stop_orca_context()
        ctx = init_zoo_context(mesh_shape={"data": 2, "model": 4})
        try:
            assert ctx.mesh.axis_names == ("data", "model")
            assert ctx.mesh.devices.shape == (2, 4)
        finally:
            stop_orca_context()

    def test_bad_mesh_shape(self):
        stop_orca_context()
        with pytest.raises(ValueError):
            init_zoo_context(mesh_shape={"data": 3})
        stop_orca_context()


class TestCompilationCachePlacement:
    """The cache is placed from outside: JAX_COMPILATION_CACHE_DIR
    wins untouched, else one fixed in-checkout path from any cwd."""

    @pytest.fixture(autouse=True)
    def _restore_cache_dir(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_var_set_leaves_jax_config_untouched(self, monkeypatch,
                                                     tmp_path):
        from analytics_zoo_tpu.common.context import (
            enable_compilation_cache)

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        # a sentinel stands in for what jax read from the variable at
        # import: any directory set in code would overwrite it
        jax.config.update("jax_compilation_cache_dir", "sentinel-dir")
        enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == "sentinel-dir"

    def test_env_var_unset_resolves_fixed_checkout_path(
            self, monkeypatch, tmp_path):
        from analytics_zoo_tpu.common.context import (
            COMPILE_CACHE_DIR, enable_compilation_cache)

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.chdir(tmp_path)
        jax.config.update("jax_compilation_cache_dir", None)
        enable_compilation_cache()
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        assert COMPILE_CACHE_DIR == os.path.join(repo, ".xla_cache")
        assert jax.config.jax_compilation_cache_dir == COMPILE_CACHE_DIR


class TestTriggers:
    def test_every_epoch(self):
        t = EveryEpoch()
        assert t(TriggerState(epoch=1, iteration=10, epoch_finished=True))
        assert not t(TriggerState(epoch=1, iteration=10, epoch_finished=False))

    def test_several_iteration(self):
        t = SeveralIteration(3)
        fired = [i for i in range(1, 10)
                 if t(TriggerState(iteration=i))]
        assert fired == [3, 6, 9]

    def test_max_triggers(self):
        assert MaxEpoch(2)(TriggerState(epoch=2))
        assert not MaxEpoch(2)(TriggerState(epoch=1))
        assert MaxIteration(5)(TriggerState(iteration=5))
        assert MaxScore(0.9)(TriggerState(score=0.95))
        assert not MaxScore(0.9)(TriggerState(score=None))
        assert MinLoss(0.1)(TriggerState(loss=0.05))

    def test_and_or_composition(self):
        s = TriggerState(epoch=3, iteration=30, epoch_finished=True, loss=0.5)
        assert And(EveryEpoch(), MaxEpoch(2))(s)
        assert not And(EveryEpoch(), MinLoss(0.1))(s)
        assert Or(MinLoss(0.1), MaxEpoch(3))(s)
        assert (EveryEpoch() & MaxEpoch(2))(s)
        assert (MinLoss(0.1) | MaxEpoch(3))(s)


class TestTimer:
    def test_timing_stats(self):
        timer = Timer()
        for _ in range(5):
            with timer.timing("stage"):
                pass
        stat = timer.stat("stage")
        assert stat.count == 5
        assert stat.total >= 0
        assert len(stat.top(3)) == 3
        assert "stage" in stat.summary()
