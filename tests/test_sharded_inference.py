"""Sharded serving layer (ISSUE-7): compile-cache key isolation,
warm_up under an active mesh, numerical parity (exact modes bitwise-
close, quantized collectives within the documented tolerance), the
auto heuristic, and the serving-surface wiring (worker metrics +
/debug/vars shard blocks).

Runs the real SPMD path on the conftest 8-device CPU mesh.
"""

import jax
import numpy as np
import pytest

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.inference.inference_model import InferenceModel
from analytics_zoo_tpu.inference.sharded import resolve_shard_plan
from analytics_zoo_tpu.keras.layers.transformer import TransformerModule

VOCAB, SEQ, HIDDEN = 32, 8, 16

_SHARD_KEYS = (
    "zoo.serving.shard.mode",
    "zoo.serving.shard.recipe",
    "zoo.serving.shard.quantized_collectives",
    "zoo.serving.shard.devices",
    "zoo.serving.shard.auto_hbm_bytes",
    "zoo.serving.shard.auto_hbm_fraction",
)


@pytest.fixture(autouse=True)
def _clean_shard_config():
    yield
    cfg = get_config()
    for key in _SHARD_KEYS:
        cfg.unset(key)


@pytest.fixture(scope="module")
def tiny_transformer():
    module = TransformerModule(vocab=VOCAB, seq_len=SEQ,
                               hidden_size=HIDDEN, n_head=2, n_block=1,
                               hidden_dropout=0.0, attn_dropout=0.0)
    x = np.random.RandomState(0).randint(0, VOCAB,
                                         (5, SEQ)).astype(np.int32)
    variables = module.init(jax.random.PRNGKey(0), x)
    return module, variables, x


def _model(tiny_transformer) -> InferenceModel:
    module, variables, _ = tiny_transformer
    return InferenceModel().load_flax(module, variables=variables)


def _set(mode, **kv):
    cfg = get_config()
    cfg.set("zoo.serving.shard.mode", mode)
    for k, v in kv.items():
        cfg.set("zoo.serving.shard." + k, v)


class TestCacheKeys:
    def test_mode_off_hits_exact_pre_mesh_keys(self, tiny_transformer):
        """mode=off keys are the plain (shape, dtype) tuples of the
        pre-mesh engine -- warm persistent caches survive the
        upgrade (no plan signature, no wrapper)."""
        _, _, x = tiny_transformer
        m = _model(tiny_transformer)
        m.shard()  # default config: mode off -> no-op
        assert m.shard_plan is None
        m.predict(x)
        assert list(m._compiled) == [(((8, SEQ), "int32"),)]

    def test_sharded_keys_never_collide_across_meshes(
            self, tiny_transformer):
        """Same bucket under different plans -> distinct cache
        entries: off vs tp vs dp vs tp-on-a-smaller-device-set all
        carry distinguishable keys."""
        _, _, x = tiny_transformer
        keys = {}
        for name, mode, extra in (
                ("off", "off", {}),
                ("tp8", "tp", {}),
                ("dp8", "dp", {}),
                ("tp2", "tp", {"devices": 2}),
                ("tp8_q8", "tp", {"quantized_collectives": True})):
            _set(mode, **extra)
            m = _model(tiny_transformer).shard()
            m.predict(x)
            keys[name] = next(iter(m._compiled))
            for k in ("zoo.serving.shard.devices",
                      "zoo.serving.shard.quantized_collectives"):
                get_config().unset(k)
        assert len(set(keys.values())) == len(keys), keys
        # every sharded key embeds the unchanged shape tuple, so the
        # bucket identity is still first-class
        shape_key = keys["off"]
        for name in ("tp8", "dp8", "tp2", "tp8_q8"):
            assert keys[name][0] == shape_key, keys[name]

    def test_plan_signature_carries_device_set(self, tiny_transformer):
        _, variables, _ = tiny_transformer
        _set("tp")
        full = resolve_shard_plan(variables)
        _set("tp", devices=2)
        half = resolve_shard_plan(variables)
        assert full.signature != half.signature
        assert full.n_devices == 8 and half.n_devices == 2


class TestWarmUp:
    def test_warm_up_under_mesh_snaps_and_covers_ladder(
            self, tiny_transformer):
        """Under a batch-splitting plan the ladder snaps to mesh-size
        multiples; warmed sizes then serve with zero fresh compiles."""
        _, _, x = tiny_transformer
        _set("dp")
        m = _model(tiny_transformer).shard()
        assert m.shard_plan.batch_multiple == 8
        m.warm_up(x[:1], batch_sizes=(1, 8, 32))
        # buckets 1 and 8 both snap to 8 -> exactly two entries
        assert len(m._compiled) == 2
        before = set(m._compiled)
        m.predict(x[:3])   # -> bucket 8
        m.predict(np.repeat(x, 4, axis=0)[:20])  # -> bucket 32
        assert set(m._compiled) == before

    def test_bucket_for_is_a_fixed_point(self, tiny_transformer):
        _set("dp", devices=2)
        m = _model(tiny_transformer).shard()
        for n in (1, 2, 3, 8, 9, 31):
            b = m._bucket_for(n)
            assert b >= n and b % 2 == 0
            assert m._bucket_for(b) == b


class TestParity:
    def _ref(self, tiny_transformer):
        _, _, x = tiny_transformer
        return np.asarray(_model(tiny_transformer).predict(x)), x

    def test_tp_matches_single_chip(self, tiny_transformer):
        ref, x = self._ref(tiny_transformer)
        _set("tp")
        out = np.asarray(_model(tiny_transformer).shard().predict(x))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_dp_matches_single_chip(self, tiny_transformer):
        ref, x = self._ref(tiny_transformer)
        _set("dp")
        out = np.asarray(_model(tiny_transformer).shard().predict(x))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_quantized_collectives_within_documented_tolerance(
            self, tiny_transformer):
        """The int8 shard re-assembly is approximate: relative error
        bounded by the per-shard quantization step (~1/127; docs
        commit <= 5% of the output range) -- and it must actually be
        the quantized path (bit-identical output would mean the exact
        engine served the request)."""
        ref, x = self._ref(tiny_transformer)
        _set("tp", quantized_collectives=True)
        m = _model(tiny_transformer).shard()
        assert m.shard_plan.quantized
        out = np.asarray(m.predict(x))
        denom = max(np.abs(ref).max(), 1e-6)
        assert np.max(np.abs(out - ref)) / denom < 0.05
        assert np.max(np.abs(out - ref)) > 0.0


class TestAutoAndValidation:
    def test_auto_picks_tp_for_big_params_dp_for_small(
            self, tiny_transformer):
        _, variables, _ = tiny_transformer
        _set("auto", auto_hbm_bytes=1)      # tiny budget -> tp
        assert resolve_shard_plan(variables).mode == "tp"
        _set("auto", auto_hbm_bytes=1 << 40)  # huge budget -> dp
        assert resolve_shard_plan(variables).mode == "dp"

    def test_tp_rejects_non_dividing_device_count(
            self, tiny_transformer):
        _, variables, _ = tiny_transformer
        _set("tp", devices=3)  # hidden 16 % 3 != 0
        with pytest.raises(ValueError, match="not divisible"):
            resolve_shard_plan(variables)

    def test_auto_falls_back_to_dp_when_recipe_shards_nothing(self):
        import flax.linen as nn

        class Mlp(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(4, name="head")(x)

        x = np.zeros((2, 6), np.float32)
        variables = Mlp().init(jax.random.PRNGKey(0), x)
        _set("auto", auto_hbm_bytes=1)  # wants tp, but no suffix match
        plan = resolve_shard_plan(variables)
        assert plan.mode == "dp"

    def test_off_resolves_to_none_and_single_device_degrades(
            self, tiny_transformer):
        _, variables, _ = tiny_transformer
        _set("off")
        assert resolve_shard_plan(variables) is None
        _set("dp", devices=1)
        assert resolve_shard_plan(variables) is None

    def test_reshard_and_quantize_after_shard_are_rejected(
            self, tiny_transformer):
        _set("dp")
        m = _model(tiny_transformer).shard()
        with pytest.raises(RuntimeError, match="already attached"):
            m.shard(m.shard_plan)
        with pytest.raises(RuntimeError, match="quantize"):
            m.quantize(min_size=1)


class TestServingSurface:
    def _serve(self, model, n=24):
        from analytics_zoo_tpu.serving.queues import (
            InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.worker import ServingWorker

        rng = np.random.RandomState(1)
        xs = rng.randint(0, VOCAB, (n, SEQ)).astype(np.int32)
        in_q, out_q = InputQueue(), OutputQueue()
        for i in range(n):
            assert in_q.enqueue(f"s{i}", x=xs[i])
        worker = ServingWorker(model, in_q, out_q, batch_size=8)
        worker.start()
        got = {}
        import time

        deadline = time.monotonic() + 60.0
        while len(got) < n and time.monotonic() < deadline:
            item = out_q.dequeue(timeout=0.1)
            if item is not None:
                got[item[0]] = item[1]
        worker.stop()
        return worker, got, xs

    def test_worker_serves_through_mesh_and_reports_shard(
            self, tiny_transformer):
        """End-to-end: the pipelined engine answers every request
        through a dp mesh, results match single-chip, and
        worker.metrics() carries the shard block."""
        module, variables, _ = tiny_transformer
        _set("dp")
        m = _model(tiny_transformer).shard()
        worker, got, xs = self._serve(m)
        assert len(got) == 24
        metrics = worker.metrics()
        assert metrics["shard"]["mode"] == "dp"
        assert metrics["shard"]["devices"] == 8
        ref = np.asarray(module.apply(variables, xs[:1]))
        np.testing.assert_allclose(got["s0"]["output"], ref[0],
                                   rtol=1e-5, atol=1e-5)

    def test_debug_vars_exposes_serving_shard(self, tiny_transformer):
        from analytics_zoo_tpu.serving.http_frontend import HttpFrontend
        from analytics_zoo_tpu.serving.queues import (
            InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.worker import ServingWorker

        _set("tp")
        m = _model(tiny_transformer).shard()
        worker = ServingWorker(m, InputQueue(), OutputQueue())
        fe = HttpFrontend(InputQueue(), OutputQueue(), worker=worker)
        try:
            info = fe.debug_vars()["serving_shard"]
            assert info["mode"] == "tp"
            assert info["recipe"] == "transformer_tp"
            assert info["devices"] == 8
        finally:
            fe._server.server_close()

    def test_debug_vars_mode_off_is_explicit(self, tiny_transformer):
        from analytics_zoo_tpu.serving.http_frontend import HttpFrontend
        from analytics_zoo_tpu.serving.queues import (
            InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.worker import ServingWorker

        worker = ServingWorker(_model(tiny_transformer), InputQueue(),
                               OutputQueue())
        fe = HttpFrontend(InputQueue(), OutputQueue(), worker=worker)
        try:
            assert fe.debug_vars()["serving_shard"] == {"mode": "off"}
        finally:
            fe._server.server_close()


class TestLaunchIsolation:
    """Per-launch shard overrides must not leak across deployments in
    one process, and a single-chip relaunch must stop advertising a
    previous deployment's mesh."""

    def test_overrides_do_not_mutate_global_config(
            self, tiny_transformer):
        from analytics_zoo_tpu.inference.sharded import (
            maybe_shard_from_config)

        m = _model(tiny_transformer)
        plan = maybe_shard_from_config(
            m, overrides={"zoo.serving.shard.mode": "dp"})
        assert plan is not None and plan.mode == "dp"
        # the config layer never saw the override...
        assert get_config().get("zoo.serving.shard.mode") == "off"
        # ...so a second deployment without a shard block stays
        # single-chip instead of inheriting dp
        m2 = _model(tiny_transformer)
        assert maybe_shard_from_config(m2) is None
        assert m2.shard_plan is None

    def test_off_relaunch_zeroes_the_mesh_gauge(self,
                                                tiny_transformer):
        from analytics_zoo_tpu.inference.sharded import (
            _M_MESH, maybe_shard_from_config)

        maybe_shard_from_config(
            _model(tiny_transformer),
            overrides={"zoo.serving.shard.mode": "tp"})
        assert _M_MESH.labels(mode="tp").value == 8
        maybe_shard_from_config(_model(tiny_transformer))  # mode off
        assert _M_MESH.labels(mode="tp").value == 0

    def test_launcher_shard_block_is_validated(self):
        from analytics_zoo_tpu.common.config import (
            validate_config_value)

        with pytest.raises(ValueError):
            validate_config_value("zoo.serving.shard.devices", -1)
        with pytest.raises(ValueError):
            validate_config_value("zoo.serving.shard.mode", "tpx")


class TestQuantizedCollectives:
    """The EQuARX-idiom primitives themselves, against the exact
    collectives on the 8-device mesh."""

    def _mesh(self):
        from analytics_zoo_tpu.parallel import create_mesh

        return create_mesh({"data": 8})

    def test_quantized_psum_tracks_exact_psum(self):
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu.parallel.collectives import (
            quantized_psum)
        from analytics_zoo_tpu.parallel.mesh import shard_map

        mesh = self._mesh()
        x = np.random.RandomState(0).randn(16, 12).astype(np.float32)

        def exact(v):
            return lax.psum(v, "data")

        def approx(v):
            return quantized_psum(v, "data")

        spec = P("data")
        ref = shard_map(exact, mesh, (spec,), spec)(x)
        got = shard_map(approx, mesh, (spec,), spec)(x)
        denom = max(np.abs(np.asarray(ref)).max(), 1e-6)
        rel = np.max(np.abs(np.asarray(got) - np.asarray(ref))) / denom
        # 8 shards x <=1/254 quantization step each, relative to the
        # per-shard max -- comfortably inside the documented 5% bound
        assert rel < 0.05, rel

    def test_quantized_psum_exact_on_zeros(self):
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu.parallel.collectives import (
            quantized_psum)
        from analytics_zoo_tpu.parallel.mesh import shard_map

        mesh = self._mesh()
        x = np.zeros((8, 4), np.float32)
        out = shard_map(lambda v: quantized_psum(v, "data"), mesh,
                        (P("data"),), P("data"))(x)
        assert np.all(np.asarray(out) == 0.0)

    def test_quantized_all_gather_concatenates_in_shard_order(self):
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu.parallel.collectives import (
            quantized_all_gather)
        from analytics_zoo_tpu.parallel.mesh import shard_map

        mesh = self._mesh()
        x = np.random.RandomState(1).randn(16, 4).astype(np.float32)

        def gather(v):
            return quantized_all_gather(v, "data", axis=0)

        out = np.asarray(shard_map(gather, mesh, (P("data"),),
                                   P("data"))(x))
        # every shard reconstructs the full [16, 4] array; out_specs
        # stacks the 8 copies -> [128, 4]. Each copy must match the
        # input in shard order within one int8 quantization step.
        assert out.shape == (8 * 16, 4)
        for copy in out.reshape(8, 16, 4):
            assert np.abs(copy - x).max() <= np.abs(x).max() / 127 + 1e-6
