"""The Ouro cell as data and as a run: the configuration file against
the catalog row, the FLOPs function against the issue's hand counts,
the per-layer readers on a made-up reduction, the reference's faults,
the three older decoders left as they were, and the cell end to end
under the rehearsal switch."""

import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.models.text import sparse_decoder_lm
from benchmark.layer_metrics import (
    loop_exit_expected_steps, train_attention_loop_device_ms,
    train_attention_loop_roofline, train_loop_head_device_ms,
    train_loop_head_roofline, train_loop_remat_device_ms)
from benchmark.lib import flops_ouro, loop_scopes, scope_reduce
from benchmark.reference import ouro as ref
from benchmark.runners.train_fit import _build

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ouro-2.6b.fit-b1-l8192-packed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "vocab_size", "total_ut_steps")
TRACE_READERS = (train_loop_head_device_ms, train_loop_head_roofline,
                 train_attention_loop_device_ms,
                 train_attention_loop_roofline, train_loop_remat_device_ms)


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "ouro-2.6b.json")


@pytest.fixture(scope="module")
def data():
    return _load("benchmark", "workloads", CELL + ".json")["data"]


def test_flops_function_reproduces_the_hand_counts(config, data):
    """ISSUE 38's arithmetic: a layer's products count
    ``total_ut_steps`` times, its parameters once."""
    assert flops_ouro.layer_params(config) == 51_388_416
    assert flops_ouro.params(config) == 612_438_017
    assert flops_ouro.params(config) * 16 == 9_799_008_272       # 9.80 GB
    # six layers, the fallback the memory reading did not force
    assert flops_ouro.params(dict(config, num_hidden_layers=6)) == 509_661_185
    assert 2 * flops_ouro.matmul_params_per_token(config) == 4_093_640_704
    parts = flops_ouro.forward_flops_per_token(config, 8192)
    assert parts["heads"] == 805_306_368
    assert parts["projections"] + parts["swiglu"] + parts["heads"] == (
        4_093_640_704)
    attention = 32 * flops_ouro.attention_forward_flops(config, 8192)
    assert attention == pytest.approx(8.80e12, rel=1e-3)
    work = flops_ouro.train(config, data)
    forward = work["flops_per_sample"] / 3
    assert forward == pytest.approx(42.33e12, rel=1e-3)
    assert work["flops_per_sample"] == pytest.approx(127.0e12, rel=1e-3)
    assert work["flops_per_sample"] / 197e12 == pytest.approx(0.645, abs=1e-3)
    assert 8192 * parts["heads"] / forward == pytest.approx(0.156, abs=1e-3)
    assert attention / forward == pytest.approx(0.208, abs=1e-3)
    # at the published depth the heads are 3.0 % of the forward FLOPs
    deep = dict(config, num_hidden_layers=48)
    whole = flops_ouro.train(deep, data)["flops_per_sample"] / 3
    assert 8192 * parts["heads"] / whole == pytest.approx(0.030, abs=1e-3)
    # one pass of it is a plain dense decoder's count
    once = dict(config, total_ut_steps=1)
    assert flops_ouro.train(once, data)["flops_per_sample"] * 4 == (
        work["flops_per_sample"])
    assert flops_ouro.params(once) == flops_ouro.params(config)
    assert flops_ouro.heads_train_flops(config, 8192) == (
        3 * 4 * 2 * 2048 * 49152 * 8192)
    assert flops_ouro.attention_train_flops(config, 8192) == (
        3 * (8192 * 8193 // 2) * 4 * 128 * 16 * 8 * 4)
    # the optimizer walks each parameter once: 36 bytes a parameter
    assert work["min_bytes_per_step"] == 612_438_017 * 36 + 8192 * 8


def test_config_keeps_every_published_key_but_the_depth(config):
    assert config["reduced"] == ["num_hidden_layers"]
    assert set(config["reduced"]) == set(config["reduced_why"]) == set(
        config["published"])
    assert config["num_hidden_layers"] == 8
    assert config["published"]["num_hidden_layers"] == 48
    assert 48 % config["num_hidden_layers"] == 0        # whole stages
    assert config["total_ut_steps"] == 4
    assert config["head_dim"] * config["num_attention_heads"] == config[
        "hidden_size"]
    for key in ("beta", "initialisation", "rope", "norm_inside_the_loop",
                "loss", "document_mask", "compute_dtype",
                "rematerialisation", "optimizer", "layer_types",
                "source_of_unkeyed_choices"):
        assert config["assumed"][key], key
        assert "TBD" not in config["assumed"][key], key
    assert "TBD" not in config["reference"]["tolerance_why"]
    assert "TBD" not in config["reduced_why"]["num_hidden_layers"]
    assert "TBD" not in config["deployment"]
    cell = _load("benchmark", "workloads", CELL + ".json")
    assert "TBD" not in cell["why"]
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog in this installation")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f if '"Ouro-2.6B"' in line)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    for key in WIDTHS:
        assert key not in config["reduced"]


def test_benchmark_entries_name_the_cell_and_its_six_metrics():
    bench = _load("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert entry["file"] == "benchmark/configs/ouro-2.6b.json"
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"].startswith(
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "ouro-2.6b"
    assert cell["traffic"] == "fit-b1-l8192-packed"
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == sorted(
        r.__name__.rsplit(".", 1)[1]
        for r in TRACE_READERS + (loop_exit_expected_steps,))
    assert all(m["moves"] == "train_samples_per_s" for m in mine)
    # no older metric's list was widened
    assert not any(CELL in m.get("workloads", []) for m in bench["per_layer"]
                   if m not in mine)


def test_rehearsal_sizes_live_in_the_rehearsal_group_only(config):
    tiny = config["rehearsal"]
    assert tiny["hidden_size"] < 128 < config["hidden_size"]
    assert set(tiny) <= set(config)
    assert "total_ut_steps" not in tiny          # the loop is the model


def test_cell_is_the_decoder_cells_traffic_but_for_the_epochs_length(data):
    other = _load("benchmark", "workloads",
                  "trinity-mini.fit-b1-l8192-packed.json")["data"]
    assert {k: v for k, v in data.items() if k != "steps_per_epoch"} == {
        k: v for k, v in other.items() if k != "steps_per_epoch"}
    assert data["steps_per_epoch"] == 2


# ------------------------------------------------------------------ #
# readers                                                            #
# ------------------------------------------------------------------ #
def _row(scope, ms):
    return {"scope": scope, "total_ms": ms}


BODY = "while/body/closed_call/stack/loop_body"


@pytest.fixture()
def ctx(monkeypatch, config, data):
    reduced = {
        "attention_ms": {"attention_flash": 300.0},
        "modules": [
            _row(f"{BODY}/layer_*/attention/q", 30.0),
            _row(f"{BODY}/layer_*/attention/attention_flash", 80.0),
            _row(f"{BODY}/stack/loop_body/checkpoint/layer_*/attention/"
                 "attention_flash", 140.0),
            _row(f"{BODY}/stack/loop_body/checkpoint/rematted_computation/"
                 "layer_*/attention/attention_flash", 80.0),
            _row(f"{BODY}/stack/loop_body/checkpoint/rematted_computation/"
                 "layer_*/mlp/w1", 120.0),
            _row("loop_head", 60.0),
            _row("loop_head/while/body", 110.0),
            _row("loop_head/LoopedDecoderModule._count", 1.0),
            _row("exit_loss", 9.0),
            _row("exit_loss/tbld,dbl->tbl", 20.0),
            _row("optimizer", 25.0),
        ]}
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: reduced)
    return {
        "config": config, "cell": {"name": CELL, "data": data},
        "chips": 1, "device_kind": "TPU v5 lite",
        "window": {"batch": 1, "steps": 16, "steps_per_epoch": 2},
        "trace": {"busy_s": 2.5}}


def test_readers_on_a_made_up_reduction(ctx):
    assert train_loop_head_device_ms.read(ctx) == 200.0
    assert train_attention_loop_device_ms.read(ctx) == 300.0
    assert train_loop_remat_device_ms.read(ctx) == 200.0
    # 3 x 4 x 2 x 2048 x 49152 x 8192 at 197 TFLOP/s = 100.5 ms
    heads_s = 3 * 4 * 2 * 2048 * 49152 * 8192 / 197e12
    assert heads_s == pytest.approx(0.1005, rel=1e-3)
    assert train_loop_head_roofline.read(ctx) == pytest.approx(
        100 * heads_s / 0.200)
    # 3 x 33,558,528 pairs x 4 x 128 x 16 x 32 applications = 134 ms
    attention_s = 3 * 33_558_528 * 4 * 128 * 16 * 32 / 197e12
    assert attention_s == pytest.approx(0.1340, rel=1e-3)
    assert train_attention_loop_roofline.read(ctx) == pytest.approx(
        100 * attention_s / 0.300)
    for reader in (train_loop_head_roofline, train_attention_loop_roofline):
        assert 0 < reader.read(ctx) < 100


def test_readers_find_nothing_on_a_program_without_the_scopes(
        ctx, monkeypatch):
    """As the parent commit or another model reads: no such scope, no
    such counter, no such key -- nothing is returned, nothing raises."""
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: {
        "attention_ms": {},
        "modules": [_row("layer_*/attention/q", 3.0),
                    _row("optimizer", 1.0)]})
    for reader in TRACE_READERS:
        assert reader.read(ctx) is None, reader.__name__
    # another configuration's keys: the shares read nothing
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: {
        "attention_ms": {"attention_flash": 5.0},
        "modules": [_row("loop_head", 3.0)]})
    other = dict(ctx, config=_load("benchmark", "configs",
                                   "evabyte-6.5b.json"))
    assert train_loop_head_roofline.read(other) is None
    assert train_attention_loop_roofline.read(other) is None
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: None)
    for reader in TRACE_READERS:
        assert reader.read(ctx) is None, reader.__name__
    assert loop_scopes.exit_expected_steps({}) is None
    assert loop_scopes.exit_expected_steps(
        {loop_scopes.STEPS: {"values": {"module=,index=0": 0.0}}}) is None


def test_expected_exit_step_from_the_counters():
    snapshot = {
        loop_scopes.STEPS: {"values": {"module=,index=0": 10.0}},
        loop_scopes.EXIT_PROBABILITY: {"values": {
            "module=,index=0": 5_000.0, "module=,index=1": 2_500.0,
            "module=,index=2": 1_250.0, "module=,index=3": 1_250.0}}}
    assert loop_scopes.exit_expected_steps(snapshot) == pytest.approx(1.875)


# ------------------------------------------------------------------ #
# the reference's faults                                             #
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def tiny(config):
    """The rehearsal's model on seeded weights in float32, a row of 64
    ids and the reference's ``z_T`` on it."""
    small = {**config, **config["rehearsal"], "compute_dtype": "float32"}
    model = _build(small, "model")
    ids = np.random.default_rng(11).integers(
        1, small["vocab_size"], (1, 64)).astype(np.int32)
    variables = model.estimator.adapter.init(
        jax.random.PRNGKey(11), {"input_ids": ids})
    return small, model, variables, ids, ref.forward(variables, ids, small)


def _error(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_program_matches_the_reference_at_the_rehearsal_size(tiny):
    small, model, variables, ids, right = tiny
    got, _ = model.estimator.adapter.apply(variables, {"input_ids": ids},
                                           training=False)
    assert _error(got, right) < 2e-5


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_on_the_reference_moves_the_error(tiny, fault):
    """Fresh weights in float32: each fault of the configuration's
    table lands far over the comparison's own error (2e-5)."""
    small, _, variables, ids, right = tiny
    with ref.faulty(fault):
        wrong = ref.forward(variables, ids, small)
    assert _error(wrong, right) > 1e-3, fault


def test_float8_operands_read_far_over_bfloat16_ones(tiny):
    small, _, variables, ids, right = tiny
    with ref.operands_rounded_to(jnp.bfloat16):
        bf16 = _error(ref.forward(variables, ids, small), right)
    with ref.operands_rounded_to(jnp.float8_e4m3fn):
        fp8 = _error(ref.forward(variables, ids, small), right)
    assert 0 < bf16 < 0.02 and fp8 > 4 * bf16


# ------------------------------------------------------------------ #
# the three older decoders share `_rematerialised` with the new one  #
# ------------------------------------------------------------------ #
KEPT_BEFORE = (
    "flash_attention_out", "flash_attention_lse", "swiglu_gate", "swiglu_up",
    "attention_q", "attention_k", "attention_v", "attention_k_rot",
    "eva_k_summary", "eva_v_summary", "attention_q_proj", "attention_gate",
    "attention_out", "mlp_in", "mlp_out")
# parameter tree (sha256 of the sorted paths and shapes), loss and the
# gradient's norm of each module at a tiny size on seed 7, as the
# parent commit (PR 36's tree) gives them on this backend
OLDER = {
    "sparse": ("58c53d8ad41375cf", 4.161808490753174, 2.054750442504883),
    "latent": ("119908975c49ccff", 4.160111904144287, 2.079469680786133),
    "byte": ("49a3681e783f2867", 4.18302583694458, 0.8558987379074097),
}


def _older(name):
    lm = sparse_decoder_lm
    if name == "sparse":
        return lm.SparseDecoderModule(
            vocab=64, hidden_size=32,
            layer_types=("sliding_attention", "full_attention"),
            n_dense_layers=1, n_head=4, n_kv_head=2, head_dim=8, window=8,
            dense_width=48, expert_width=16, n_routed=8, n_held=8, top_k=2,
            shared_width=16), lm.next_token_loss
    if name == "latent":
        return lm.LatentDecoderModule(
            vocab=64, hidden_size=32, n_layers=2, n_dense_layers=1, n_head=4,
            nope_dim=8, rope_dim=4, v_dim=8, latent_dim=16, dense_width=48,
            expert_width=16, n_routed=8, n_held=8, top_k=2,
            shared_width=16), lm.next_token_loss
    return lm.ByteDecoderModule(
        vocab=64, hidden_size=32, n_layers=2, n_head=4, head_dim=8,
        window=8, chunk=4, dense_width=48,
        n_pred_heads=2), lm.multi_byte_loss


def test_kept_names_of_the_older_decoders_are_the_parents():
    assert sparse_decoder_lm.KEPT_NAMES == KEPT_BEFORE
    # the default policy is still theirs
    assert sparse_decoder_lm._rematerialised.__defaults__ == (KEPT_BEFORE,)


@pytest.mark.parametrize("name", sorted(OLDER))
def test_older_decoder_has_the_parents_tree_and_loss(name):
    module, loss_of = _older(name)
    ids = jnp.asarray(np.random.default_rng(7).integers(0, 64, (2, 24)),
                      jnp.int32)
    variables = module.init(jax.random.PRNGKey(7), ids)
    tree = sorted((jax.tree_util.keystr(p), tuple(a.shape)) for p, a in
                  jax.tree_util.tree_leaves_with_path(variables["params"]))
    digest = hashlib.sha256(json.dumps(tree).encode()).hexdigest()[:16]

    def loss(params):
        out = module.apply(
            {**variables, "params": params}, ids, train=True,
            mutable=[k for k in variables if k != "params"])[0]
        return loss_of(out, jnp.roll(ids, -1, 1))

    value, grads = jax.value_and_grad(loss)(variables["params"])
    norm = float(jnp.sqrt(sum(jnp.sum(g * g)
                              for g in jax.tree_util.tree_leaves(grads))))
    want_digest, want_loss, want_norm = OLDER[name]
    assert digest == want_digest
    assert float(value) == pytest.approx(want_loss, rel=1e-6)
    assert norm == pytest.approx(want_norm, rel=1e-5)


# ------------------------------------------------------------------ #
# the cell, end to end                                               #
# ------------------------------------------------------------------ #
def test_cell_rehearses_correct_with_no_compile_in_the_window():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               ZOO_BENCH_REHEARSAL="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 38), "--seconds", "1",
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


def test_parent_program_fails_cleanly_on_an_unknown_cell():
    """What the parent commit does with this cell's name: no entry in
    ``BENCHMARK.json``, exit code 3 before JAX is touched."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "ouro-2.6b.no-such-traffic", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=REPO, capture_output=True,
        text=True, timeout=120)
    assert p.returncode == 3
    assert "no cell 'ouro-2.6b.no-such-traffic' in BENCHMARK.json" in p.stderr
    assert p.stdout.strip() == ""
