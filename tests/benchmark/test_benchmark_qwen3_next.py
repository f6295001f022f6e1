"""The Qwen3-Next-80B-A3B cell as data and as a run: the configuration
file against the catalog row, the FLOPs function against the issue's
hand counts and the initialised model, the per-layer readers on a
made-up reduction, the reference's faults against bfloat16 rounding, and
the cell end to end under the rehearsal switch."""

import importlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark.layer_metrics import (
    moe_held_load_over_expected, train_attention_gated_roofline,
    train_delta_rule_device_ms, train_delta_rule_roofline,
    train_linear_mixer_device_ms)
from benchmark.lib import flops_qwen3_next, scope_reduce
from benchmark.reference import qwen3_next as ref
from benchmark.runners.train_fit import _build

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "qwen3-next-80b-a3b.fit-b1-l8192-packed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "head_dim", "moe_intermediate_size",
          "shared_expert_intermediate_size", "linear_key_head_dim",
          "linear_value_head_dim", "linear_num_key_heads",
          "linear_num_value_heads", "linear_conv_kernel_dim",
          "num_attention_heads", "num_key_value_heads",
          "num_experts_per_tok", "partial_rotary_factor")
READERS = (train_delta_rule_device_ms, train_delta_rule_roofline,
           train_linear_mixer_device_ms, train_attention_gated_roofline,
           moe_held_load_over_expected)
# accepted metrics whose ``workloads`` this cell was appended to
APPENDED = ("train_attention_device_ms", "train_moe_device_ms",
            "train_moe_routing_device_ms", "train_moe_experts_roofline",
            "moe_expert_load_max_over_mean", "moe_assignments_dropped",
            "train_remat_device_ms")


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "qwen3-next-80b-a3b.json")


@pytest.fixture(scope="module")
def data():
    return _load("benchmark", "workloads", CELL + ".json")["data"]


def test_flops_function_reproduces_the_hand_counts(config, data):
    assert flops_qwen3_next.linear_params(config) == 33_718_464
    assert flops_qwen3_next.attention_params(config) == 27_263_488
    assert flops_qwen3_next.moe_params(config) == 104_859_648
    assert flops_qwen3_next.params(config) == 625_667_136
    assert flops_qwen3_next.held_per_token(config) == 0.625
    # forward a token: GDN projections 202 M, attention 67 M, the delta
    # rule 17 M over three layers, 468 M in all; 11.5 TFLOP trained
    assert 3 * 2 * flops_qwen3_next.linear_matmul_params(
        config) == pytest.approx(202.1e6, rel=1e-3)
    assert flops_qwen3_next.attention_forward_flops(
        config, 8192) / 8192 == pytest.approx(67.117e6, rel=1e-4)
    assert 3 * flops_qwen3_next.delta_rule_forward_flops(
        config, 8192) / 8192 == pytest.approx(17.43e6, rel=1e-3)
    work = flops_qwen3_next.train(config, data)
    assert isinstance(work["flops_per_sample"], int)
    assert work["flops_per_sample"] / 3 / 8192 == pytest.approx(
        468.28e6, rel=1e-4)
    assert work["min_bytes_per_step"] == 625_667_136 * 36 + 8192 * 8
    # the delta rule's least bytes a layer: q, k, v, g, beta and o
    # twice, the inputs' gradients, 128 float32 states of 32 heads
    assert flops_qwen3_next.delta_rule_train_min_bytes(
        config, 8192) == 1_012_924_416


def test_parameter_count_is_the_initialised_models(config):
    """``flops_qwen3_next.params`` against the model the cell builds,
    by shape alone (nothing is allocated)."""
    model = _build(config, "model")
    shapes = jax.eval_shape(
        model.estimator.adapter.init, jax.random.PRNGKey(0),
        {"input_ids": np.zeros((1, 128), np.int32)})
    counted = sum(int(np.prod(a.shape))
                  for a in jax.tree_util.tree_leaves(shapes["params"]))
    assert counted == flops_qwen3_next.params(config) == 625_667_136
    p = shapes["params"]
    assert p["layer_0"]["linear_mixer"]["in_proj_qkvz"]["kernel"].shape == (
        2048, 12288)
    assert p["layer_0"]["linear_mixer"]["conv"].shape == (4, 8192)
    assert p["layer_3"]["attention"]["q"]["kernel"].shape == (2048, 4096)
    assert p["layer_3"]["attention"]["k"]["kernel"].shape == (2048, 512)
    assert p["layer_2"]["moe"]["w1"].shape == (32, 2048, 512)
    assert p["layer_2"]["moe"]["router"]["kernel"].shape == (2048, 512)
    assert p["head"].shape == (2048, 18992)
    assert shapes["router_state"]["layer_0"]["moe"]["bias"].shape == (512,)


def test_config_keeps_every_published_key_but_the_reduced(config):
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert set(config["reduced"]) == set(config["reduced_why"]) == set(
        config["published"])
    # floors: one whole period (3 + 1), >= 8 routed experts held, >= an
    # eighth of the vocabulary
    assert config["num_hidden_layers"] % config[
        "full_attention_interval"] == 0
    assert config["num_experts"] >= 8
    assert config["num_experts_routed_over"] == config["published"][
        "num_experts"] == 512
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert config["decoder_sparse_step"] == 1 and config[
        "mlp_only_layers"] == []
    for key in ("norms", "column_layouts", "short_conv", "delta_rule",
                "gates_initialisation", "rope", "softmax_scale", "router",
                "shared_expert", "aux_loss", "mtp", "embedding",
                "compute_dtype", "optimizer", "document_mask",
                "rematerialisation", "initialisation"):
        assert config["assumed"][key]
        assert "TO BE FILLED" not in config["assumed"][key], key
    assert "TO BE FILLED" not in config["reference"]["tolerance_why"]
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog in this installation")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Qwen3-Next-80B-A3B-Instruct"' in line)
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


def test_cell_is_the_other_decoder_cells_traffic_to_the_digit(data):
    for other in ("trinity-mini", "moonlight-16b-a3b"):
        assert data == _load("benchmark", "workloads",
                             f"{other}.fit-b1-l8192-packed.json")["data"]


def test_benchmark_names_the_cell_and_its_five_metrics():
    """By name, never by position: a later PR appends after them."""
    bench = _load("BENCHMARK.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["config"] == "qwen3-next-80b-a3b" and cell["chips"] == 1
    assert "qwen3-next-80b-a3b" in [c["name"] for c in bench["configs"]]
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for reader in READERS:
        entry = metrics[reader.__name__.rsplit(".", 1)[1]]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_samples_per_s"


# ------------------------------------------------------------------ #
# readers                                                            #
# ------------------------------------------------------------------ #
def _row(scope, ms):
    return {"scope": scope, "total_ms": ms}


@pytest.fixture()
def ctx(monkeypatch, config, data):
    reduced = {
        "attention_ms": {"attention_flash": 16.0},
        "modules": [
            _row("layer_*/linear_mixer/in_proj_qkvz", 10.0),
            _row("layer_*/linear_mixer/short_conv", 2.0),
            _row("layer_*/linear_mixer/delta_gates", 3.0),
            _row("layer_*/linear_mixer/delta_rule", 20.0),
            _row("layer_*/linear_mixer/delta_rule/while/body", 15.0),
            _row("checkpoint/rematted_computation/layer_*/linear_mixer/"
                 "delta_rule/while/body", 5.0),
            _row("layer_*/linear_mixer/gated_norm", 1.0),
            _row("layer_*/linear_mixer/out_proj", 4.0),
            _row("layer_*/attention/attention_flash", 16.0),
            _row("layer_*/moe/moe_shared/shared/w1", 8.0),
            _row("layer_*/moe/moe_route/router", 2.0),
            _row("layer_*/moe/moe_experts", 6.0),
            _row("optimizer", 100.0),
        ]}
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: reduced)
    return {
        "config": config, "cell": {"name": CELL, "data": data},
        "chips": 1, "device_kind": "TPU v5 lite",
        "window": {"batch": 1, "steps": 64, "samples": 64,
                   "steps_per_epoch": 8},
        "trace": {"busy_s": 1.0},
        "moe": {"moe_assignments_held": {
            f"layer_{i}/moe": [160 * 64] * 32
            for i in range(4)},
            "moe_assignments_dropped": {
                f"layer_{i}/moe": [0] for i in range(4)},
            "moe_expert_assignments": {
                f"layer_{i}/moe": [160 * 64] * 32 for i in range(4)}}}


def test_readers_on_a_reduction(ctx):
    assert train_delta_rule_device_ms.read(ctx) == 40.0
    assert train_linear_mixer_device_ms.read(ctx) == 60.0
    # bandwidth-bound: 3 layers x 1,012,924,416 B at 819 GB/s
    least_s = 3 * 1_012_924_416 / 819e9
    assert train_delta_rule_roofline.read(ctx) == pytest.approx(
        100 * least_s / 0.040)
    # 1 attention layer x 33,558,528 pairs x 2 x 512 x 16 x 3 at peak
    least_s = (8192 * 8193 // 2) * 2 * 512 * 16 * 3 / 197e12
    assert train_attention_gated_roofline.read(ctx) == pytest.approx(
        100 * least_s / 0.016)
    assert 0 < train_attention_gated_roofline.read(ctx) < 100
    # 160 held assignments an expert and step over 64 steps: balanced
    assert moe_held_load_over_expected.read(ctx) == pytest.approx(1.0)


@pytest.mark.parametrize("name", APPENDED)
def test_accepted_metrics_list_the_cell_and_read_it(ctx, name):
    """The expert layer's, the remat's and the attention dispatcher's
    accepted readers read this cell's program too: appended at the end
    of their ``workloads``, nothing else of the entry changed."""
    entry, = [m for m in _load("BENCHMARK.json")["per_layer"]
              if m["name"] == name]
    assert entry["workloads"][-1] == CELL
    assert CELL not in entry["workloads"][:-1]
    value = importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(ctx)
    assert value is not None and value >= 0
    if name.endswith("_roofline"):
        assert value < 100


def test_readers_find_nothing_on_a_program_without_the_scopes(
        ctx, monkeypatch):
    """As the parent (or another cell's program) reads: no such scope
    or counter -- nothing is returned and nothing raises."""
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: {
        "attention_ms": {},
        "modules": [_row("layer_*/attention/q", 3.0),
                    _row("optimizer", 1.0)]})
    bare = dict(ctx, moe={})
    for reader in READERS:
        assert reader.read(bare) is None, reader.__name__
    other = dict(ctx, config=_load("benchmark", "configs",
                                   "trinity-mini.json"))
    for reader in READERS:
        assert reader.read(other) is None, reader.__name__
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: None)
    for reader in READERS[:4]:
        assert reader.read(ctx) is None, reader.__name__


# ------------------------------------------------------------------ #
# the reference's faults                                             #
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def tiny(config):
    """The rehearsal's model with weights from a seed and one row."""
    small = {**config, **config["rehearsal"], "delta_rule_chunk": 16}
    model = _build(small, "model")
    rng = np.random.default_rng(0)
    x = {"input_ids": rng.integers(0, small["vocab_size"], (1, 48)).astype(
        np.int32)}
    variables = model.estimator.adapter.init(jax.random.PRNGKey(0), x)
    return small, variables, x


def _error(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_on_the_reference_moves_the_error(tiny, fault):
    """The cell's comparison (relative L2 of the logits) sees each
    mistake: far over float32 rounding."""
    small, variables, x = tiny
    want = ref.forward(variables, x, small)
    with ref.faulty(fault):
        wrong = _error(ref.forward(variables, x, small), want)
    assert wrong > 1e-3, (fault, wrong)


def test_float8_operands_read_far_over_bfloat16_ones(tiny):
    small, variables, x = tiny
    want = ref.forward(variables, x, small)
    with ref.operands_rounded_to(jax.numpy.bfloat16):
        bf16 = _error(ref.forward(variables, x, small), want)
    with ref.operands_rounded_to(jax.numpy.float8_e4m3fn):
        fp8 = _error(ref.forward(variables, x, small), want)
    assert fp8 > 3 * bf16


# ------------------------------------------------------------------ #
# the cell, end to end                                               #
# ------------------------------------------------------------------ #
def test_cell_rehearses_correct_with_no_compile_in_the_window():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               ZOO_BENCH_REHEARSAL="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 40), "--seconds", "1",
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
    assert detail["routing_agreement"] > 0.9
    assert detail["reference_error"] < detail["reference_tolerance"]
    counted = detail["moe_counters_in_window"]["moe_assignments"]
    assert sorted(counted) == [f"layer_{i}/moe" for i in range(4)]
