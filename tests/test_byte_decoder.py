"""The byte decoder against its plain reference
(``benchmark/reference/evabyte.py``) at tiny widths on the CPU: EVA's
joint softmax against a brute-force masked softmax, the kernels in
interpret mode against the path that holds the scores, the pooling, the
layer and the model with their gradients, causality, the multi-head
loss, and the whole model through ``Estimator``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.layers.byte_decoder import (
    ByteDecoderLayer, chunk_summaries)
from analytics_zoo_tpu.keras.layers.sparse_decoder import RMSNorm
from analytics_zoo_tpu.learn.optim import AdamWeightDecay
from analytics_zoo_tpu.models.text import ByteDecoderLM
from analytics_zoo_tpu.models.text.sparse_decoder_lm import (
    ByteDecoderModule, multi_byte_loss)
from analytics_zoo_tpu.obs.metrics import get_registry
from analytics_zoo_tpu.ops import attention, pallas_attention
from analytics_zoo_tpu.ops.attention import (
    eva_attention, eva_attention_path)
from benchmark.reference import evabyte as ref

CONFIG = dict(
    hidden_size=64, num_attention_heads=2, intermediate_size=96,
    window_size=32, chunk_size=4, num_pred_heads=3, vocab_size=50,
    num_hidden_layers=2, rms_norm_eps=1e-5, rope_theta=100000)
LENGTH = 128            # four windows


def _model(dtype="float32", **changes):
    c = {**CONFIG, **changes}
    heads = c["num_attention_heads"]
    return c, ByteDecoderLM(
        vocab=c["vocab_size"], hidden_size=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_head=heads,
        head_dim=c["hidden_size"] // heads, window=c["window_size"],
        chunk=c["chunk_size"], dense_width=c["intermediate_size"],
        n_pred_heads=c["num_pred_heads"], rope_theta=c["rope_theta"],
        eps=c["rms_norm_eps"], init_std=0.05, dtype=dtype)


def _seeded(model, seed=0, length=LENGTH, rows=2):
    """Variables from the seed (norm offsets moved off 0, so that the
    unit offset shows), ids and next-byte labels."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, model._config["vocab"], (rows, length + 1))
    x, y = ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)
    variables = model.estimator.adapter.init(jax.random.PRNGKey(seed),
                                             {"input_ids": x})
    variables["params"] = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.asarray(rng.normal(0, 0.2, a.shape), jnp.float32)
        if path[-1].key == "scale" else a, variables["params"])
    return variables, x, y


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def _qkv(rng, heads, length, d, chunk, dtype=jnp.float32):
    q, k, v = (jnp.asarray(rng.normal(size=(1, heads, length, d)), dtype)
               for _ in range(3))
    k_sum, v_sum = (jnp.asarray(
        rng.normal(size=(1, heads, length // chunk, d)), dtype)
        for _ in range(2))
    return q, k, v, k_sum, v_sum


# ------------------------------------------------------------------ #
# the joint softmax                                                  #
# ------------------------------------------------------------------ #
def _brute_force(q, k, v, k_sum, v_sum, window, chunk):
    """Row by row in numpy: the allowed token keys and summaries
    gathered, one softmax over them."""
    q, k, v, k_sum, v_sum = (np.asarray(a, np.float64)
                             for a in (q, k, v, k_sum, v_sum))
    out = np.zeros_like(q)
    scale = q.shape[-1] ** -0.5
    for h in range(q.shape[1]):
        for i in range(q.shape[2]):
            w = i // window
            keys = np.concatenate([k[0, h, w * window:i + 1],
                                   k_sum[0, h, :w * window // chunk]])
            values = np.concatenate([v[0, h, w * window:i + 1],
                                     v_sum[0, h, :w * window // chunk]])
            s = keys @ q[0, h, i] * scale
            p = np.exp(s - s.max())
            out[0, h, i] = p @ values / p.sum()
    return out


def test_joint_softmax_matches_brute_force_over_the_concatenated_keys():
    args = _qkv(np.random.default_rng(0), 2, 128, 16, 4)
    got = eva_attention(*args, window=32)
    assert _rel(got, _brute_force(*args, 32, 4)) < 2e-6
    # the reference's own joint softmax, a block of rows at a time
    want = ref.eva_attention(*(a[0].transpose(1, 0, 2) for a in args),
                             32, 4, 16 ** -0.5)
    assert _rel(got[0].transpose(1, 0, 2), want) < 2e-6


def test_one_window_reads_no_summary():
    q, k, v, k_sum, v_sum = _qkv(np.random.default_rng(1), 2, 32, 16, 4)
    got = eva_attention(q, k, v, k_sum, v_sum, window=32)
    want = attention.reference_attention(q, k, v, causal=True)
    assert _rel(got, want) < 2e-6


def test_dispatcher_rule_and_scope_name(monkeypatch):
    assert eva_attention_path("tpu", 8192, 2048, 16, 128, 32) == "flash"
    assert eva_attention_path("cpu", 8192, 2048, 16, 128, 32) == "einsum"
    # a window's summaries must fill 128-row blocks; windows must be whole
    assert eva_attention_path("tpu", 8192, 2048, 32, 128, 32) == "einsum"
    assert eva_attention_path("tpu", 8192 + 128, 2048, 16, 128, 32) == "einsum"
    assert eva_attention_path("tpu", 1024, 256, 2, 64, 2) == "einsum"
    assert eva_attention_path("tpu", 2048, 1024, 8, 64, 2) == "flash"
    args = _qkv(np.random.default_rng(0), 2, 64, 16, 4)
    text = str(jax.make_jaxpr(
        lambda *a: eva_attention(*a, window=32))(*args).pretty_print(
            name_stack=True))
    assert "attention_einsum_eva" in text
    with pytest.raises(ValueError):
        eva_attention(*_qkv(np.random.default_rng(0), 2, 48, 16, 4),
                      window=32)


@pytest.mark.parametrize("rows", ["window_boundary", "inside_a_window"])
def test_kernels_in_interpret_mode_match_the_einsum_path(rows):
    """Forward and all five gradients of the owned kernels' joint call
    against the path that holds the scores, with the cotangent on the
    rows around a window boundary or well inside a window (so that a
    fault in either part's backward cannot hide in the sum)."""
    window, chunk, d = 256, 2, 64
    args = _qkv(np.random.default_rng(2), 2, 4 * window, d, chunk)
    scale = d ** -0.5
    weight = np.zeros((1, 1, 4 * window, 1), np.float32)
    if rows == "window_boundary":
        weight[:, :, 2 * window - 3:2 * window + 3] = 1.0
    else:
        weight[:, :, 3 * window + 100:3 * window + 140] = 1.0
    cot = jnp.asarray(np.random.default_rng(3).normal(
        size=args[0].shape), jnp.float32) * weight

    def kernels(*a):
        return pallas_attention.pallas_eva_attention(*a, window, scale)

    def held(*a):
        return attention._einsum_eva_attention(*a, window, scale)

    assert _rel(kernels(*args), held(*args)) < 2e-6
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) * cot),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: jnp.sum(held(*a) * cot),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip(("dq", "dk", "dv", "dk_summary", "dv_summary"),
                          got, want):
        assert _rel(g, w) < 5e-6, name
    # rows of window 2 read no summary of windows 2 and 3
    if rows == "window_boundary":
        assert float(jnp.abs(got[3][:, :, 2 * window // chunk:]).max()) == 0


def test_pairs_counted_at_the_published_shape():
    pairs = pallas_attention.eva_pairs(8192, 2048, 128)
    assert pairs["allowed"] == 8_392_704 + 1_572_864
    # forward: 3 blocks of 1024^2 a window; backward: 10 of 512^2
    assert pairs["computed_forward"] == 4 * 3 * 1024 ** 2 + 1_572_864
    assert pairs["computed_backward"] == 4 * 10 * 512 ** 2 + 1_572_864


# ------------------------------------------------------------------ #
# pooling, layer and model against the reference                     #
# ------------------------------------------------------------------ #
def test_chunk_summaries_match_reference_with_gradients():
    rng = np.random.default_rng(4)
    k, v = (jnp.asarray(rng.normal(size=(1, 2, 64, 16)), jnp.float32)
            for _ in range(2))
    phi, mu = (jnp.asarray(rng.normal(size=(2, 16)), jnp.float32)
               for _ in range(2))
    cot = [jnp.asarray(rng.normal(size=(1, 2, 16, 16)), jnp.float32)
           for _ in range(2)]

    def ours(k, v, phi, mu):
        return chunk_summaries(k, v, phi, mu, 4, 0.25)

    def theirs(k, v, phi, mu):
        k_sum, v_sum = ref.chunk_summaries(
            k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2), phi, mu, 4,
            0.25)
        return (k_sum.transpose(1, 0, 2)[None], v_sum.transpose(1, 0, 2)[None])

    def scalar(fn):
        return lambda *a: sum(jnp.sum(o * c) for o, c in zip(fn(*a), cot))

    for got, want in zip(ours(k, v, phi, mu), theirs(k, v, phi, mu)):
        assert _rel(got, want) < 2e-6
    got = jax.grad(scalar(ours), argnums=(0, 1, 2, 3))(k, v, phi, mu)
    want = jax.grad(scalar(theirs), argnums=(0, 1, 2, 3))(k, v, phi, mu)
    for name, g, w in zip(("dk", "dv", "dphi", "dmu"), got, want):
        assert _rel(g, w) < 5e-6, name


def test_layer_matches_reference():
    c, model = _model()
    variables, x, _ = _seeded(model)
    lp = variables["params"]["layer_0"]
    h = jnp.asarray(np.random.default_rng(5).normal(
        size=(1, LENGTH, c["hidden_size"])), jnp.float32)
    layer = ByteDecoderLayer(
        attention=dict(n_head=2, head_dim=32, window=32, chunk=4,
                       rope_theta=c["rope_theta"]),
        dense_width=c["intermediate_size"], eps=c["rms_norm_eps"])
    got = layer.apply({"params": lp}, h)
    w = ref.weights_from_program(variables)["layers"][0]
    with jax.default_matmul_precision("highest"):
        m = h[0] + ref.attention_branch(
            ref.rms_norm_1p(h[0], w["input_norm"], c["rms_norm_eps"]), w, c)
        want = m + ref.swiglu(
            ref.rms_norm_1p(m, w["pre_mlp_norm"], c["rms_norm_eps"]),
            w["mlp"])
    assert got.dtype == jnp.float32
    assert _rel(got[0], want) < 2e-5


def test_model_matches_reference_in_float32():
    """Logits of every head, the multi-byte loss and every gradient
    (k's and v's through the pooling, ``adaptive_phi`` and
    ``adaptive_mu_k`` included) against the plain reference."""
    c, model = _model()
    variables, x, y = _seeded(model)
    adapter = model.estimator.adapter
    logits, _ = adapter.apply(variables, {"input_ids": x}, training=False)
    want = ref.forward(variables, x, c)
    assert logits.shape == (2, LENGTH, 3, 50) and logits.dtype == jnp.float32
    assert _rel(logits, want) < 2e-5

    def loss(params):
        out, _ = adapter.apply({**variables, "params": params},
                               {"input_ids": x}, training=True)
        return multi_byte_loss(out, y)

    got_loss, got = jax.value_and_grad(loss)(variables["params"])
    want_loss, want = ref.loss_and_grads(variables, x, y, c)
    assert abs(float(got_loss) - float(want_loss)) < 2e-5
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert len(flat_got) == len(flat_want)
    for path, g in flat_got:
        name = jax.tree_util.keystr(path)
        assert _rel(g, flat_want[path]) < 5e-5, name
        assert float(jnp.abs(g).max()) > 0, name


def test_model_matches_reference_in_bfloat16():
    c, model = _model(dtype="bfloat16")
    variables, x, _ = _seeded(model)
    logits, _ = model.estimator.adapter.apply(
        variables, {"input_ids": x}, training=False)
    assert logits.dtype == jnp.float32
    assert _rel(logits, ref.forward(variables, x, c)) < 0.03


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS
                                   if f != "labels_shifted"])
def test_each_fault_moves_the_reference(fault):
    c, model = _model()
    variables, x, _ = _seeded(model)
    want = ref.forward(variables, x, c)
    with ref.faulty(fault):
        assert _rel(ref.forward(variables, x, c), want) > 1e-3, fault


def test_model_has_two_unit_offset_norms_a_layer_and_eight_heads():
    module = ByteDecoderModule(
        vocab=320, hidden_size=64, n_layers=1, n_head=2, head_dim=32,
        window=32, chunk=4, dense_width=96, n_pred_heads=8)
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 64), jnp.int32))
    p = shapes["params"]
    assert set(p["layer_0"]) == {"attention", "input_norm", "pre_mlp_norm",
                                 "mlp"}
    assert set(p["layer_0"]["attention"]) == {
        "q", "k", "v", "out", "adaptive_phi", "adaptive_mu_k"}
    assert p["layer_0"]["attention"]["adaptive_phi"].shape == (2, 32)
    assert p["head"].shape == (64, 8 * 320)
    # the unit offset is a field of the one RMSNorm: its parameter
    # starts at 0 and the scale at 1
    norm = RMSNorm(unit_offset=True)
    v = norm.init(jax.random.PRNGKey(0), jnp.ones((1, 4)))
    assert float(jnp.abs(v["params"]["scale"]).max()) == 0
    x = jnp.asarray([[1.0, -2.0, 3.0, 0.5]])
    plain = RMSNorm()
    assert _rel(norm.apply(v, x), plain.apply(
        plain.init(jax.random.PRNGKey(0), x), x)) < 1e-7
    moved = {"params": {"scale": jnp.full((4,), 0.5)}}
    assert _rel(norm.apply(moved, x), 1.5 * norm.apply(v, x)) < 1e-7


# ------------------------------------------------------------------ #
# causality                                                          #
# ------------------------------------------------------------------ #
def test_perturbing_a_byte_moves_nothing_before_it():
    """Byte t changed: no logit before t moves; and of the layer's
    summaries only those of t's own chunk move, which only windows
    after t's own read."""
    c, model = _model()
    variables, x, _ = _seeded(model, rows=1)
    adapter = model.estimator.adapter
    t = 45                                   # window 1, chunk 11
    other = x.copy()
    other[0, t] = (x[0, t] + 7) % c["vocab_size"]
    a, _ = adapter.apply(variables, {"input_ids": x}, training=False)
    b, _ = adapter.apply(variables, {"input_ids": other}, training=False)
    moved = np.abs(np.asarray(a - b)).max(axis=(0, 2, 3))       # [L]
    assert moved[:t].max() == 0
    assert moved[t] > 0 and moved[64:].max() > 0

    # the summaries of one layer: only chunk t // 4 moves
    w = ref.weights_from_program(variables)
    layer = w["layers"][0]

    def summaries(ids):
        h = ref.rms_norm_1p(w["embed"][ids], layer["input_norm"], 1e-5)
        k = ref.rope((h @ layer["wk"]).reshape(-1, 2, 32), c["rope_theta"])
        v = (h @ layer["wv"]).reshape(-1, 2, 32)
        return ref.chunk_summaries(k, v, layer["phi"], layer["mu"], 4,
                                   32 ** -0.5)

    for s_a, s_b in zip(summaries(x[0]), summaries(other[0])):
        changed = np.abs(np.asarray(s_a - s_b)).max(axis=(1, 2)) > 0
        assert list(np.nonzero(changed)[0]) == [t // 4]
    # and rows of t's own window past t read the byte itself, never
    # its summary: with the summaries of window 1 scrambled, windows 0
    # and 1 read the same
    args = list(_qkv(np.random.default_rng(6), 2, 128, 16, 4))
    base = eva_attention(*args, window=32)
    args[3] = args[3].at[:, :, 8:16].add(1.0)
    args[4] = args[4].at[:, :, 8:16].add(1.0)
    scrambled = eva_attention(*args, window=32)
    assert float(jnp.abs(base - scrambled)[:, :, :64].max()) == 0
    assert float(jnp.abs(base - scrambled)[:, :, 64:].max()) > 0


# ------------------------------------------------------------------ #
# the loss                                                           #
# ------------------------------------------------------------------ #
def test_multi_byte_loss_is_the_loop_over_heads_with_the_tail_masked():
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(2, 12, 3, 9)), jnp.float32)
    labels = rng.integers(0, 9, (2, 12)).astype(np.int32)
    total, count = 0.0, 0
    for n in range(3):
        for b in range(2):
            for t in range(12 - n):
                row = np.asarray(logits[b, t, n], np.float64)
                total += np.log(np.exp(row).sum()) - row[labels[b, t + n]]
                count += 1
    assert count == 2 * (12 + 11 + 10)
    assert float(multi_byte_loss(logits, labels)) == pytest.approx(
        total / count, rel=1e-6)
    # a label in the masked tail changes nothing for the heads past 0...
    moved = labels.copy()
    moved[:, -1] = (moved[:, -1] + 1) % 9
    one_head = multi_byte_loss(logits[:, :, :1], labels)
    assert float(multi_byte_loss(logits[:, :, :1], moved)) != float(one_head)
    # ...and head 0 alone is the next-token loss
    from analytics_zoo_tpu.models.text.sparse_decoder_lm import (
        next_token_loss)
    assert float(one_head) == pytest.approx(
        float(next_token_loss(logits[:, :, 0], labels)), rel=1e-6)
    # the reference's fault: one head's labels shifted by one
    c, model = _model()
    variables, x, y = _seeded(model)
    right = float(ref.loss(variables, x, y, c))
    with ref.faulty("labels_shifted"):
        assert abs(float(ref.loss(variables, x, y, c)) - right) > 1e-4


# ------------------------------------------------------------------ #
# rematerialisation and the Estimator                                #
# ------------------------------------------------------------------ #
def test_remat_layer_backward_keeps_the_joint_output(monkeypatch):
    """At three windows of 1,024 with 128 summaries each the flash path
    serves the call: a layer holds 1 + 2 forward kernels and 1 + 2
    backward ones, and its second forward none, because the joint
    output and logsumexp are kept by name. Traced only."""
    monkeypatch.setattr(attention, "_platform", lambda q: "tpu")
    module = ByteDecoderModule(
        vocab=64, hidden_size=128, n_layers=2, n_head=2, head_dim=64,
        window=1024, chunk=8, dense_width=96, n_pred_heads=2)
    ids = jnp.zeros((1, 3072), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            ids)["params"]

    def loss(params):
        return multi_byte_loss(module.apply({"params": params}, ids),
                               jnp.roll(ids, -1, 1))

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    text = str(jaxpr)
    assert text.count("pallas_call[") == 2 * 6
    assert "name=flash_attention_out" in text
    # q, k, v and the chunk summaries as the kernels read them, the
    # branch's output, the MLP's input and its two pre-activations
    for name in ("attention_q", "attention_k", "attention_v",
                 "eva_k_summary", "eva_v_summary", "attention_out",
                 "mlp_in", "swiglu_gate", "swiglu_up"):
        assert text.count(f"name={name}]") == 2, name
    assert "attention_flash_eva" in jaxpr.pretty_print(name_stack=True)


def test_fit_predict_counters_and_gauge():
    """compile / fit / predict like the other zoo models: the loss
    falls, every head's loss publishes under the model's counters, the
    pairs gauge is set."""
    _, model = _model()
    rng = np.random.default_rng(3)
    prior = 1.0 / np.arange(1, 50) ** 1.1
    ids = rng.choice(np.arange(1, 50), size=(16, LENGTH + 1),
                     p=prior / prior.sum()).astype(np.int32)
    x, y = {"input_ids": ids[:, :-1]}, ids[:, 1:]
    model.compile(optimizer=AdamWeightDecay(lr=3e-3), seed=0)

    def published(name):
        family = get_registry().snapshot().get(name)
        return dict((family or {"values": {}})["values"])

    loss_name = "zoo_model_multibyte_head_loss_micronats_total"
    steps_name = "zoo_model_multibyte_head_steps_total"
    before = published(loss_name), published(steps_name)
    history = model.fit((x, y), batch_size=8, epochs=3)
    assert history[-1]["loss"] < history[0]["loss"]
    logits = model.predict(x, batch_size=8)
    assert logits.shape == (16, LENGTH, 3, 50) and logits.dtype == np.float32
    after = published(loss_name), published(steps_name)
    steps = sum(after[1].values()) - sum(before[1].values())
    assert steps == 3 * 2
    per_head = [(after[0][f"module=,index={i}"]
                 - before[0].get(f"module=,index={i}", 0)) / steps / 1e6
                for i in range(3)]
    # each head's mean over the epochs lies between the last and the
    # first epoch's loss, give or take the heads' spread
    assert all(0.5 * history[-1]["loss"] < v < 1.5 * history[0]["loss"]
               for v in per_head), per_head
    gauge = published("zoo_model_attention_eva_pairs_computed_ratio")
    # set while this model's step was traced (the registry is the
    # process's: other modules' entries may stand beside these)
    assert gauge["module=layer_0/attention"] == 1.0
    assert gauge["module=layer_1/attention"] == 1.0
