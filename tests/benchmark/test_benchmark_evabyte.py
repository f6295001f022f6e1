"""The EvaByte cell as data and as a run: the configuration file against
the catalog row, the FLOPs function against the issue's hand counts and
the initialised model, the per-layer readers on a made-up reduction,
the reference's faults, and the cell end to end under the rehearsal
switch."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark.layer_metrics import (
    attention_eva_pairs_computed_over_allowed, train_attention_eva_device_ms,
    train_attention_eva_roofline, train_chunk_summaries_device_ms)
from benchmark.lib import eva_scopes, flops_evabyte, scope_reduce
from benchmark.reference import evabyte as ref
from benchmark.runners import train_fit_lm
from benchmark.runners.train_fit import _build

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "evabyte-6.5b.fit-b1-l8192-packed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "window_size", "chunk_size",
          "num_pred_heads", "vocab_size")
READERS = (train_attention_eva_device_ms, train_attention_eva_roofline,
           train_chunk_summaries_device_ms,
           attention_eva_pairs_computed_over_allowed)


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "evabyte-6.5b.json")


@pytest.fixture(scope="module")
def data():
    return _load("benchmark", "workloads", CELL + ".json")["data"]


def test_flops_function_reproduces_the_hand_counts(config, data):
    assert flops_evabyte.layer_params(config) == 202_391_552
    assert flops_evabyte.head_params(config) == 10_485_760
    assert flops_evabyte.params(config) == 821_366_784
    # five layers: 16.4 GB of state at 16 bytes a parameter
    assert flops_evabyte.params(
        dict(config, num_hidden_layers=5)) * 16 == 16_380_133_376
    assert flops_evabyte.attention_pairs(config, 8192) == {
        "in_window": 8_392_704, "summaries": 1_572_864}
    # one window reads no summary; a ragged tail counts its own rows
    assert flops_evabyte.attention_pairs(config, 2048)["summaries"] == 0
    assert flops_evabyte.attention_pairs(config, 2048 + 16) == {
        "in_window": 2048 * 2049 // 2 + 16 * 17 // 2, "summaries": 16 * 128}
    per_token = flops_evabyte.attention_forward_flops(config, 8192) / 8192
    assert per_token == pytest.approx(19.93e6, rel=1e-3)
    parts = flops_evabyte.forward_flops_per_token(config, 8192)
    # 404.8 MFLOP a byte and layer in the matrix products
    assert (parts["projections"] + parts["swiglu"]) / 4 == pytest.approx(
        404.8e6, rel=1e-3)
    assert parts["attention"] / sum(parts.values()) == pytest.approx(
        0.046, abs=0.002)
    work = flops_evabyte.train(config, data)
    assert isinstance(work["flops_per_sample"], int)
    assert work["flops_per_sample"] == 42_263_283_499_008     # 42.3 TFLOP
    assert work["flops_per_sample"] == 3 * 8192 * sum(parts.values())
    assert work["min_bytes_per_step"] == 821_366_784 * 36 + 8192 * 8
    pooled = flops_evabyte.chunk_summaries_min_bytes(config, 8192)
    # k and v read (2 x 67 MB), 512 summaries of each written
    assert pooled["forward"] == 2 * 8192 * 4096 * 2 + 2 * 512 * 4096 * 2
    assert pooled["backward"] > pooled["forward"]


def test_parameter_count_is_the_initialised_models(config):
    """``flops_evabyte.params`` against the model the cell builds, by
    shape alone (nothing is allocated)."""
    model = _build(config, "model")
    shapes = jax.eval_shape(
        model.estimator.adapter.init, jax.random.PRNGKey(0),
        {"input_ids": np.zeros((1, 4096), np.int32)})
    counted = sum(int(np.prod(a.shape))
                  for a in jax.tree_util.tree_leaves(shapes["params"]))
    assert counted == flops_evabyte.params(config) == 821_366_784
    layer = shapes["params"]["layer_3"]
    assert "layer_4" not in shapes["params"]
    assert layer["attention"]["adaptive_phi"].shape == (32, 128)
    assert layer["attention"]["adaptive_mu_k"].shape == (32, 128)
    assert layer["mlp"]["w1"]["kernel"].shape == (4096, 11008)
    assert shapes["params"]["head"].shape == (4096, 8 * 320)
    assert shapes["params"]["embed"]["embedding"].shape == (320, 4096)
    per_layer = sum(int(np.prod(a.shape))
                    for a in jax.tree_util.tree_leaves(layer))
    assert per_layer == 202_391_552


def test_config_keeps_every_published_key_but_the_depth(config):
    assert config["reduced"] == ["num_hidden_layers"]
    assert set(config["reduced"]) == set(config["reduced_why"]) == set(
        config["published"])
    assert config["num_hidden_layers"] == 4      # the floor
    assert config["published"]["num_hidden_layers"] == 32
    assert config["head_dim"] * config["num_attention_heads"] == config[
        "hidden_size"]
    for key in ("pooling", "mask", "adaptive_init", "loss", "rope", "norms",
                "compute_dtype", "optimizer", "initialisation",
                "document_mask", "rematerialisation", "head_dim"):
        assert config["assumed"][key]
        assert "TO BE FILLED" not in config["assumed"][key], key
    assert "TO BE FILLED" not in config["reference"]["tolerance_why"]
    cell = _load("benchmark", "workloads", CELL + ".json")
    assert "TO BE FILLED" not in cell["why"]
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog in this installation")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f if '"EvaByte"' in line)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    for key in WIDTHS:
        assert key not in config["reduced"]


def test_rehearsal_sizes_live_in_the_rehearsal_group_only(config):
    tiny = config["rehearsal"]
    assert tiny["hidden_size"] < 128 < config["hidden_size"]
    assert set(tiny) <= set(config)


def test_cell_is_the_decoder_cells_traffic_in_bytes(data):
    """The other decoder cells' generator and shape; documents four
    times as long (bytes for tokens), half as many steps an epoch."""
    other = _load("benchmark", "workloads",
                  "trinity-mini.fit-b1-l8192-packed.json")["data"]
    assert {k: v for k, v in data.items()
            if k not in ("doc_len_median", "steps_per_epoch")} == {
        k: v for k, v in other.items()
        if k not in ("doc_len_median", "steps_per_epoch")}
    assert data["doc_len_median"] == 4 * other["doc_len_median"] == 2400
    assert data["steps_per_epoch"] == 4


# ------------------------------------------------------------------ #
# readers                                                            #
# ------------------------------------------------------------------ #
def _row(scope, ms):
    return {"scope": scope, "total_ms": ms}


@pytest.fixture()
def ctx(monkeypatch, config, data):
    reduced = {
        "attention_ms": {"attention_flash_eva": 40.0},
        "modules": [
            _row("layer_*/attention/q", 3.0),
            _row("layer_*/attention/eva_chunk_summaries", 2.0),
            _row("checkpoint/layer_*/attention/eva_chunk_summaries", 4.5),
            _row("checkpoint/rematted_computation/layer_*/attention/"
                 "eva_chunk_summaries", 1.5),
            _row("layer_*/attention/attention_flash_eva", 12.0),
            _row("checkpoint/layer_*/attention/attention_flash_eva", 28.0),
            _row("multibyte_head/final_norm", 1.0),
            _row("optimizer", 100.0),
        ]}
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: reduced)
    return {
        "config": config, "cell": {"name": CELL, "data": data},
        "chips": 1, "device_kind": "TPU v5 lite",
        "window": {"batch": 1, "steps": 40, "steps_per_epoch": 4},
        "trace": {"busy_s": 1.0},
        "gauges": {eva_scopes.PAIRS_GAUGE: {
            "layer_0/attention": 1.315, "layer_1/attention": 1.315}}}


def test_readers_on_a_made_up_reduction(ctx):
    assert train_attention_eva_device_ms.read(ctx) == 40.0
    assert train_chunk_summaries_device_ms.read(ctx) == 8.0
    assert attention_eva_pairs_computed_over_allowed.read(ctx) == 1.315
    # 4 layers x 9,965,568 pairs x 4 x 128 x 32 x 3 at 197 TFLOP/s
    least_s = 4 * 9_965_568 * 4 * 128 * 32 * 3 / 197e12
    assert least_s == pytest.approx(9.945e-3, rel=1e-3)
    assert train_attention_eva_roofline.read(ctx) == pytest.approx(
        100 * least_s / 0.040)
    assert 0 < train_attention_eva_roofline.read(ctx) < 100


def test_readers_find_nothing_on_a_program_without_the_scopes(
        ctx, monkeypatch):
    """As a program without EVA attention reads (the parent commit, or
    another model): no such scope, no such gauge -- nothing is returned
    and nothing raises."""
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: {
        "attention_ms": {"attention_flash": 5.0, "attention_flash_window": 9.0,
                         "attention_flash_latent": 7.0},
        "modules": [_row("layer_*/attention/q", 3.0),
                    _row("optimizer", 1.0)]})
    bare = {k: v for k, v in ctx.items() if k != "gauges"}
    for reader in READERS:
        assert reader.read(bare) is None, reader.__name__
    assert attention_eva_pairs_computed_over_allowed.read(
        dict(bare, gauges={})) is None
    other = dict(ctx, config=_load("benchmark", "configs",
                                   "trinity-mini.json"))
    assert train_attention_eva_roofline.read(other) is None
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: None)
    for reader in READERS[:3]:
        assert reader.read(ctx) is None, reader.__name__


def test_runner_reads_head_losses_and_gauges_off_the_registry():
    loss, steps = train_fit_lm.HEAD_LOSS, train_fit_lm.HEAD_STEPS
    before = {loss: {"": {0: 1e6, 1: 2e6}}, steps: {"": {0: 2}}}
    after = {loss: {"": {0: 13e6, 1: 20e6}}, steps: {"": {0: 6}}}
    assert train_fit_lm._head_losses(before, after) == [3.0, 4.5]
    assert train_fit_lm._head_losses(before, before) is None
    empty = {loss: {}, steps: {}}
    assert train_fit_lm._head_losses(empty, empty) is None
    assert train_fit_lm._by_labels(
        {"values": {"module=layer_0/attention": 1.25,
                    "module=a,index=3": 2.0}}) == {
        "layer_0/attention": {0: 1.25}, "a": {3: 2.0}}


# ------------------------------------------------------------------ #
# the reference's faults                                             #
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def tiny(config):
    """The rehearsal's model with weights from a seed (norm offsets off
    0; ``adaptive_phi`` and ``adaptive_mu_k`` of unit size, as training
    may leave them -- they start at a twentieth of that, where the
    pooling is within a few percent of a plain mean by construction),
    and one row of ids over four windows."""
    small = {**config, **config["rehearsal"]}
    model = _build(small, "model")
    rng = np.random.default_rng(0)
    ids = rng.integers(0, small["vocab_size"], (1, 129)).astype(np.int32)
    x, y = {"input_ids": ids[:, :-1]}, ids[:, 1:]
    variables = model.estimator.adapter.init(jax.random.PRNGKey(0), x)
    sizes = {"scale": 0.2, "adaptive_phi": 1.0, "adaptive_mu_k": 1.0}
    variables["params"] = jax.tree_util.tree_map_with_path(
        lambda path, a: np.asarray(
            rng.normal(0, sizes[path[-1].key], a.shape), np.float32)
        if path[-1].key in sizes else a, variables["params"])
    return small, variables, x, y


def _error(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_on_the_reference_moves_the_error(tiny, fault):
    """The cell's comparison (relative L2 of the logits; for the one
    fault of the loss, the loss's gradients) would see each mistake the
    tolerance was set against: over the error of bfloat16-rounded
    operands at this size."""
    small, variables, x, y = tiny

    def read():
        if fault != "labels_shifted":
            return ref.forward(variables, x, small)
        grads = ref.loss_and_grads(variables, x, y, small)[1]
        return np.concatenate([np.asarray(g).ravel() for g in
                               jax.tree_util.tree_leaves(grads)])

    want = read()
    with ref.operands_rounded_to(jax.numpy.bfloat16):
        rounding = _error(read(), want)
    with ref.faulty(fault):
        wrong = _error(read(), want)
    assert rounding < 0.02
    assert wrong > 2 * rounding, (fault, wrong, rounding)


def test_float8_operands_read_far_over_bfloat16_ones(tiny):
    small, variables, x, _ = tiny
    want = ref.forward(variables, x, small)
    with ref.operands_rounded_to(jax.numpy.bfloat16):
        bf16 = _error(ref.forward(variables, x, small), want)
    with ref.operands_rounded_to(jax.numpy.float8_e4m3fn):
        fp8 = _error(ref.forward(variables, x, small), want)
    assert fp8 > 5 * bf16


# ------------------------------------------------------------------ #
# the cell, end to end                                               #
# ------------------------------------------------------------------ #
def test_cell_rehearses_correct_with_no_compile_in_the_window():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               ZOO_BENCH_REHEARSAL="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "1"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert line["metrics"]["compile_requests_in_window"]["value"] == 0
    assert all(m["unit"] == "count" for m in line["metrics"].values())
    detail = json.loads(p.stderr.strip().splitlines()[-1])["detail"]
    assert all(detail["checks"].values()), detail["checks"]
    assert detail["reference_error"] < detail["reference_tolerance"]
    # three heads in the rehearsal, each with a loss near the epochs'
    assert len(detail["head_losses"]) == 3
    assert all(0.5 * detail["epoch_losses"][-1] < v
               < 1.5 * detail["epoch_losses"][0]
               for v in detail["head_losses"])
    assert sorted(detail["gauges"][eva_scopes.PAIRS_GAUGE]) == [
        f"layer_{i}/attention" for i in range(4)]
