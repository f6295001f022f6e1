"""The Moonlight-16B-A3B cell as data and as a run: the configuration
file against the catalog row, the FLOPs function against the issue's
hand counts and the initialised model, the per-layer readers on a
made-up reduction, the reference's faults, and the cell end to end
under the rehearsal switch."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark.layer_metrics import (
    train_attention_latent_device_ms, train_attention_latent_roofline,
    train_latent_projections_device_ms)
from benchmark.lib import flops_moonlight, scope_reduce
from benchmark.reference import moonlight as ref
from benchmark.runners.train_fit import _build

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "moonlight-16b-a3b.fit-b1-l8192-packed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "num_attention_heads", "num_key_value_heads",
          "num_experts_per_tok", "n_shared_experts")


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "moonlight-16b-a3b.json")


@pytest.fixture(scope="module")
def data():
    return _load("benchmark", "workloads", CELL + ".json")["data"]


def test_flops_function_reproduces_the_hand_counts(config, data):
    assert flops_moonlight.attention_params(config) + 512 == 13_763_072
    assert flops_moonlight.expert_params(config) == 8_650_752
    assert flops_moonlight.params(config) == 668_890_112
    # with 4 routed layers, the floor: 568.5 M
    assert flops_moonlight.params(
        dict(config, num_hidden_layers=5)) == 668_890_112 - 100_405_760
    assert flops_moonlight.attention_pairs(8192) == 8192 * 8193 // 2
    # 41.9 MFLOP a token and layer in the kernels, 878 a token in all
    per_token = flops_moonlight.attention_forward_flops(config, 8192) / 8192
    assert per_token == pytest.approx(41.948e6, rel=1e-4)
    work = flops_moonlight.train(config, data)
    assert isinstance(work["flops_per_sample"], int)
    assert work["flops_per_sample"] == 21_586_186_862_592     # 21.6 TFLOP
    assert work["flops_per_sample"] / 3 / 8192 == pytest.approx(
        878.3e6, rel=1e-3)
    assert work["min_bytes_per_step"] == 668_890_112 * 36 + 8192 * 8


def test_parameter_count_is_the_initialised_models(config):
    """``flops_moonlight.params`` against the model the cell builds, by
    shape alone (nothing is allocated)."""
    model = _build(config, "model")
    shapes = jax.eval_shape(
        model.estimator.adapter.init, jax.random.PRNGKey(0),
        {"input_ids": np.zeros((1, 128), np.int32)})
    counted = sum(int(np.prod(a.shape))
                  for a in jax.tree_util.tree_leaves(shapes["params"]))
    assert counted == flops_moonlight.params(config) == 668_890_112
    bias = shapes["router_state"]["layer_1"]["moe"]["bias"]
    assert bias.shape == (64,) and "layer_0" not in shapes["router_state"]
    moe = shapes["params"]["layer_5"]["moe"]
    assert moe["w1"].shape == (8, 2048, 1408)
    assert moe["shared"]["w2"]["kernel"].shape == (2816, 2048)
    assert moe["router"]["kernel"].shape == (2048, 64)


def test_config_keeps_every_published_key_but_the_reduced(config):
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert set(config["reduced"]) == set(config["reduced_why"]) == set(
        config["published"])
    # floors of the model-configs guide: the dense layer once and >= 4
    # routed layers, >= 8 routed experts, >= an eighth of the vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["n_routed_experts_routed_over"] == config["published"][
        "n_routed_experts"] == 64
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    for key in ("norms", "rope", "softmax_scale", "kv_up_layout", "router",
                "aux_loss", "shared_experts", "embedding", "compute_dtype",
                "optimizer", "document_mask", "rematerialisation",
                "initialisation"):
        assert config["assumed"][key]
        assert "TO BE FILLED" not in config["assumed"][key], key
    assert "TO BE FILLED" not in config["reference"]["tolerance_why"]
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog in this installation")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Moonlight-16B-A3B"' in line)
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


def test_cell_is_the_trinity_cells_traffic_to_the_digit(data):
    other = _load("benchmark", "workloads",
                  "trinity-mini.fit-b1-l8192-packed.json")
    assert data == other["data"]


# ------------------------------------------------------------------ #
# readers                                                            #
# ------------------------------------------------------------------ #
def _row(scope, ms):
    return {"scope": scope, "total_ms": ms}


@pytest.fixture()
def ctx(monkeypatch, config, data):
    reduced = {
        "attention_ms": {"attention_flash_latent": 60.0},
        "modules": [
            _row("layer_*/attention/q", 3.0),
            _row("checkpoint/layer_*/attention/kv_up", 4.0),
            _row("checkpoint/rematted_computation/layer_*/attention/"
                 "latent_norm", 1.0),
            _row("layer_*/attention/latent_rope", 2.0),
            _row("checkpoint/layer_*/attention/out", 5.0),
            _row("layer_*/attention/attention_flash_latent", 20.0),
            _row("checkpoint/layer_*/attention/attention_flash_latent", 40.0),
            _row("layer_*/moe/moe_shared/shared/w1", 8.0),
            _row("optimizer", 100.0),
        ]}
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: reduced)
    return {
        "config": config, "cell": {"name": CELL, "data": data},
        "chips": 1, "device_kind": "TPU v5 lite",
        "window": {"batch": 1, "steps": 56, "steps_per_epoch": 8},
        "trace": {"busy_s": 1.0}}


def test_readers_split_the_kernels_from_the_projections(ctx):
    assert train_attention_latent_device_ms.read(ctx) == 60.0
    assert train_latent_projections_device_ms.read(ctx) == 15.0
    # 6 layers x 33,558,528 pairs x 2 x (192 + 128) x 16 x 3 at 197 TFLOP/s
    least_s = 6 * (8192 * 8193 // 2) * 2 * 320 * 16 * 3 / 197e12
    assert train_attention_latent_roofline.read(ctx) == pytest.approx(
        100 * least_s / 0.060)
    assert 0 < train_attention_latent_roofline.read(ctx) < 100


def test_readers_find_nothing_on_a_program_without_the_scopes(
        ctx, monkeypatch):
    """As a program without latent attention reads: no such scope --
    nothing is returned and nothing raises."""
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: {
        "attention_ms": {"attention_flash": 5.0, "attention_flash_window": 9.0},
        "modules": [_row("layer_*/attention/q", 3.0),
                    _row("optimizer", 1.0)]})
    readers = (train_attention_latent_device_ms,
               train_attention_latent_roofline,
               train_latent_projections_device_ms)
    for reader in readers:
        assert reader.read(ctx) is None, reader.__name__
    other = dict(ctx, config=_load("benchmark", "configs",
                                   "trinity-mini.json"))
    assert train_attention_latent_roofline.read(other) is None
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: None)
    for reader in readers:
        assert reader.read(ctx) is None, reader.__name__


# ------------------------------------------------------------------ #
# the reference's faults                                             #
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def tiny(config):
    """The rehearsal's model with weights from a seed, a random router
    bias, and one row of ids."""
    small = {**config, **config["rehearsal"]}
    model = _build(small, "model")
    rng = np.random.default_rng(0)
    x = {"input_ids": rng.integers(0, small["vocab_size"], (1, 48)).astype(
        np.int32)}
    variables = model.estimator.adapter.init(jax.random.PRNGKey(0), x)
    variables["router_state"] = jax.tree_util.tree_map(
        lambda b: np.asarray(rng.normal(0, 0.2, b.shape), np.float32),
        variables["router_state"])
    return small, variables, x


def _error(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_on_the_reference_moves_the_error(tiny, fault):
    """The cell's comparison (relative L2 of the logits) would see each
    mistake the tolerance was set against: far over float32 rounding
    and over the error of bfloat16-rounded operands at this size."""
    small, variables, x = tiny
    want = ref.forward(variables, x, small)
    with ref.operands_rounded_to(jax.numpy.bfloat16):
        rounding = _error(ref.forward(variables, x, small), want)
    with ref.faulty(fault):
        wrong = _error(ref.forward(variables, x, small), want)
    assert rounding < 0.02
    assert wrong > 2 * rounding, (fault, wrong, rounding)


def test_float8_operands_read_far_over_bfloat16_ones(tiny):
    small, variables, x = tiny
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
    assert detail["routing_agreement"] > 0.9
    assert detail["reference_error"] < detail["reference_tolerance"]
    counted = detail["moe_counters_in_window"]["moe_assignments"]
    assert sorted(counted) == [f"layer_{i}/moe" for i in range(1, 6)]
