"""The Trinity-Mini cell as data and as a run: the configuration file
against the catalog row, the FLOPs function against the issue's hand
counts, the traffic generator, the per-layer readers on a made-up
reduction, and the cell end to end under the rehearsal switch."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.layer_metrics import (
    moe_assignments_dropped, moe_expert_load_max_over_mean,
    train_attention_full_device_ms, train_attention_full_roofline,
    train_attention_window_device_ms, train_attention_window_roofline,
    train_moe_device_ms, train_moe_experts_roofline,
    train_moe_routing_device_ms)
from benchmark.lib import flops_trinity, scope_reduce, traffic_tokens

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "trinity-mini.fit-b1-l8192-packed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_attention_heads", "num_key_value_heads",
          "num_experts_per_tok", "sliding_window", "num_shared_experts")


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "trinity-mini.json")


@pytest.fixture(scope="module")
def data():
    return _load("benchmark", "workloads", CELL + ".json")["data"]


def test_flops_function_reproduces_the_hand_counts(config, data):
    assert flops_trinity.params(config) == 705_473_792
    assert flops_trinity.attention_params(config) == 27_262_976
    assert flops_trinity.expert_params(config) == 6_291_456
    assert 2 * flops_trinity.matmul_params_per_token(config) == 553_385_984
    assert flops_trinity.attention_pairs(
        config, 8192, "sliding_attention") == 14_681_088    # 1,792.1 a row
    assert flops_trinity.attention_pairs(
        config, 8192, "full_attention") == 8192 * 8193 // 2  # 4,096.5 a row
    # a window as long as the sequence is full attention
    assert flops_trinity.attention_pairs(
        dict(config, sliding_window=64), 64, "sliding_attention") == 2080
    work = flops_trinity.train(config, data)
    assert work["flops_per_sample"] == 18_135_902_060_544    # 18.14 TFLOP
    assert isinstance(work["flops_per_sample"], int)
    assert work["min_bytes_per_step"] == 705_473_792 * 36 + 8192 * 8
    assert flops_trinity.held_expert_weight_bytes(config) == (
        4 * 16 * 6_291_456 * 2)


def test_config_keeps_every_published_key_but_the_reduced(config):
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "layer_types", "num_experts", "vocab_size"]
    assert set(config["reduced"]) == set(config["reduced_why"]) == set(
        config["published"])
    assert len(config["layer_types"]) == config["num_hidden_layers"] == 5
    assert config["layer_types"].count("full_attention") == 1
    # floors of the model-configs guide: a period + the dense layer once,
    # >= 8 routed experts, >= an eighth of the vocabulary
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    for key in ("norms", "qk_norm", "output_gate", "rope", "router",
                "embedding", "compute_dtype", "optimizer", "document_mask",
                "rematerialisation"):
        assert config["assumed"][key]
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog in this installation")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Trinity-Mini"' in line)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] is not None
        else:
            assert config[key] == value, key
    for key in WIDTHS:
        assert key not in config["reduced"]


def test_rehearsal_sizes_live_in_the_rehearsal_group_only(config):
    tiny = config["rehearsal"]
    assert tiny["hidden_size"] < 128 < config["hidden_size"]
    assert set(tiny) <= set(config)


def test_traffic_is_seeded_packed_and_next_token(config, data):
    small = dict(data, seq_len=512, steps_per_epoch=4)
    seed = 2**31 + 977                      # the driver's seeds are large
    x, y = traffic_tokens.generate(small, config, seed)
    again, _ = traffic_tokens.generate(small, config, seed)
    other, _ = traffic_tokens.generate(small, config, seed + 1)
    ids = x["input_ids"]
    assert ids.shape == y.shape == (4, 512) and ids.dtype == np.int32
    np.testing.assert_array_equal(ids, again["input_ids"])
    assert (ids != other["input_ids"]).any()
    np.testing.assert_array_equal(ids[:, 1:], y[:, :-1])   # the next token
    assert ids.min() == 0 and ids.max() < config["vocab_size"]
    # documents: separators there, and far fewer than tokens
    separators = int((ids == 0).sum())
    assert 0 < separators < ids.size // 50
    # Zipf: the commonest id takes a large share, and it is id 1
    counts = np.bincount(ids.ravel(), minlength=4)
    assert counts[1] == counts[1:].max() > ids.size // 20
    with pytest.raises(ValueError, match="unknown traffic kind"):
        traffic_tokens.generate(dict(small, kind="token_spans"), config, 0)


# ------------------------------------------------------------------ #
# readers                                                            #
# ------------------------------------------------------------------ #
def _row(scope, ms):
    return {"scope": scope, "total_ms": ms}


@pytest.fixture()
def ctx(monkeypatch, config, data):
    reduced = {
        "attention_ms": {"attention_flash_window": 40.0,
                         "attention_flash": 20.0},
        "modules": [
            _row("layer_*/moe/moe_experts", 10.0),
            _row("checkpoint/layer_*/moe/moe_experts", 20.0),
            _row("layer_*/moe/moe_route/router", 1.0),
            _row("layer_*/moe/moe_dispatch", 2.0),
            _row("checkpoint/rematted_computation/layer_*/moe/moe_combine",
                 4.0),
            _row("layer_*/moe/moe_shared/shared/w1", 8.0),
            _row("layer_*/attention/out", 100.0),
            _row("optimizer", 100.0),
        ]}
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: reduced)
    return {
        "config": config, "cell": {"name": CELL, "data": data},
        "chips": 1, "device_kind": "TPU v5 lite",
        "window": {"batch": 1, "steps": 80, "steps_per_epoch": 8},
        "trace": {"busy_s": 1.0},
        "moe": {
            "moe_assignments_held": {"layer_1/moe": [80 * 8000.0],
                                     "layer_2/moe": [80 * 8384.0]},
            "moe_assignments_dropped": {"layer_1/moe": [0.0],
                                        "layer_2/moe": [0.0]},
            "moe_expert_assignments": {"layer_1/moe": [100.0, 300.0],
                                       "layer_2/moe": [200.0, 200.0]},
        }}


def test_readers_split_attention_and_expert_time(ctx):
    assert train_attention_window_device_ms.read(ctx) == 40.0
    assert train_attention_full_device_ms.read(ctx) == 20.0
    assert train_moe_device_ms.read(ctx) == 45.0
    assert train_moe_routing_device_ms.read(ctx) == 7.0
    # 4 sliding layers x 14,681,088 pairs x 4 x 128 x 32 x 3 at 197 TFLOP/s
    window_s = 4 * 14_681_088 * 16384 * 3 / 197e12
    assert train_attention_window_roofline.read(ctx) == pytest.approx(
        100 * window_s / 0.040)
    full_s = 8192 * 8193 // 2 * 16384 * 3 / 197e12
    assert train_attention_full_roofline.read(ctx) == pytest.approx(
        100 * full_s / 0.020)
    # 16,384 held assignments a step: FLOP-bound (3.14 ms against the
    # weights' 2.95 ms), over 30 ms under moe_experts
    flops_s = 16384 * 6_291_456 * 6 / 197e12
    assert flops_s > 3 * 4 * 16 * 6_291_456 * 2 / 819e9
    assert train_moe_experts_roofline.read(ctx) == pytest.approx(
        100 * flops_s / 0.030)
    assert moe_expert_load_max_over_mean.read(ctx) == pytest.approx(1.5)
    assert moe_assignments_dropped.read(ctx) == 0


def test_readers_find_nothing_on_a_program_without_the_scopes(
        ctx, monkeypatch):
    """As the parent commit reads: no such scope, no such counter --
    nothing is returned and nothing raises."""
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: {
        "attention_ms": {}, "modules": [_row("optimizer", 1.0)]})
    bare = {k: v for k, v in ctx.items() if k != "moe"}
    for reader in (train_attention_window_device_ms,
                   train_attention_full_device_ms,
                   train_attention_window_roofline,
                   train_attention_full_roofline, train_moe_device_ms,
                   train_moe_routing_device_ms, train_moe_experts_roofline,
                   moe_expert_load_max_over_mean, moe_assignments_dropped):
        assert reader.read(bare) is None, reader.__name__
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: None)
    assert train_moe_device_ms.read(bare) is None
    assert train_attention_full_roofline.read(bare) is None


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
    assert line["metrics"]["moe_assignments_dropped"]["value"] == 0
    assert all(m["unit"] == "count" for m in line["metrics"].values())
    detail = json.loads(p.stderr.strip().splitlines()[-1])["detail"]
    assert all(detail["checks"].values()), detail["checks"]
    assert detail["routing_agreement"] > 0.9
    assert detail["reference_error"] < detail["reference_tolerance"]
