"""The sparse decoder against its plain reference
(``benchmark/reference/trinity.py``) at tiny widths on the CPU: window
and full attention over grouped heads, the dropless expert layer and
its share of a deployment, the router's bias step, and the whole model
through ``Estimator``."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.layers.moe import DroplessExperts, grouped_dot
from analytics_zoo_tpu.keras.layers.sparse_decoder import (
    GatedGroupedAttention)
from analytics_zoo_tpu.learn.optim import AdamWeightDecay
from analytics_zoo_tpu.models.text import SparseDecoderLM
from analytics_zoo_tpu.models.text.sparse_decoder_lm import next_token_loss
from analytics_zoo_tpu.obs.metrics import get_registry
from analytics_zoo_tpu.ops.attention import (
    dot_product_attention, reference_attention)
from analytics_zoo_tpu.ops.pallas_attention import (
    _kv_bounds, _q_bounds, pallas_flash_attention_fwd)
from benchmark.reference import trinity as ref

CONFIG = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, sliding_window=6, intermediate_size=48,
    moe_intermediate_size=24, num_experts_routed_over=16, num_experts=4,
    first_expert_held=4, num_experts_per_tok=3, num_shared_experts=1,
    route_scale=2.826, route_norm=True, rms_norm_eps=1e-5,
    rope_theta=10000, mup_enabled=True, vocab_size=64,
    num_dense_layers=1, load_balance_coeff=0.001,
    layer_types=["sliding_attention", "sliding_attention",
                 "full_attention"])


def _model(dtype="float32", **changes):
    c = {**CONFIG, **changes}
    return c, SparseDecoderLM(
        vocab=c["vocab_size"], hidden_size=c["hidden_size"],
        layer_types=c["layer_types"], n_dense_layers=c["num_dense_layers"],
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"], head_dim=c["head_dim"],
        window=c["sliding_window"], dense_width=c["intermediate_size"],
        expert_width=c["moe_intermediate_size"],
        n_routed=c["num_experts_routed_over"], n_held=c["num_experts"],
        first_held=c["first_expert_held"], top_k=c["num_experts_per_tok"],
        route_scale=c["route_scale"], n_shared=c["num_shared_experts"],
        bias_step=c["load_balance_coeff"], rope_theta=c["rope_theta"],
        eps=c["rms_norm_eps"], scale_embedding=c["mup_enabled"],
        dtype=dtype)


def _seeded(model, seed=0, length=20, rows=2, bias_scale=0.2):
    """Variables from the seed (a random router bias, so that selection
    and weights differ), ids and next-token labels."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, model._config["vocab"], (rows, length + 1))
    x, y = ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)
    variables = model.estimator.adapter.init(jax.random.PRNGKey(seed),
                                             {"input_ids": x})
    variables["router_state"] = jax.tree_util.tree_map(
        lambda b: jnp.asarray(
            rng.normal(0, bias_scale, b.shape), jnp.float32),
        variables["router_state"])
    return variables, x, y


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


# ------------------------------------------------------------------ #
# attention: window, grouped heads                                   #
# ------------------------------------------------------------------ #
def _explicit_mask(lq, lk, window):
    rows = np.arange(lq)[:, None] + (lk - lq)
    keys = np.arange(lk)[None]
    keep = keys <= rows
    if window is not None:
        keep &= rows - keys < window
    return jnp.asarray(keep)[None, None]


def _qkv(h, h_kv, lq, lk, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, h, lq, d)),
            jax.random.normal(ks[1], (1, h_kv, lk, d)),
            jax.random.normal(ks[2], (1, h_kv, lk, d)),
            jax.random.normal(ks[3], (1, h, lq, d)))


@pytest.mark.parametrize("h,h_kv,lq,lk,window,blocks", [
    (4, 2, 384, 384, 200, (128, 128)),     # L no multiple of the window
    (4, 1, 256, 512, 130, (128, 256)),     # cross-length, one KV head
    (2, 2, 384, 384, None, (128, 128)),    # full, heads not grouped
    (8, 2, 256, 256, 128, (128, 128)),     # window = one block
])
def test_flash_window_and_grouped_heads_match_explicit_mask(
        h, h_kv, lq, lk, window, blocks):
    """The owned kernels in interpret mode against ``reference_attention``
    with the mask written out: values and all three gradients."""
    q, k, v, ct = _qkv(h, h_kv, lq, lk, 64)
    group = h // h_kv
    mask = _explicit_mask(lq, lk, window)

    def flash(q, k, v):
        return pallas_flash_attention_fwd(q, k, v, True, None, *blocks,
                                          window)

    def explicit(q, k, v):
        return reference_attention(q, jnp.repeat(k, group, 1),
                                   jnp.repeat(v, group, 1), mask=mask)

    np.testing.assert_allclose(flash(q, k, v), explicit(q, k, v),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(explicit(*a) * ct), (0, 1, 2))(
        q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("window", [5, 16, None])
def test_dispatcher_cpu_path_matches_explicit_mask(window):
    q, k, v, _ = _qkv(4, 2, 12, 12, 8)
    got = dot_product_attention(q, k, v, causal=True, window=window)
    want = reference_attention(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                               mask=_explicit_mask(12, 12, window))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        reference_attention(q, k, v, causal=True, window=window), want,
        atol=1e-5, rtol=1e-5)


def test_window_needs_causal_and_heads_must_divide():
    q, k, v, _ = _qkv(4, 3, 8, 8, 8)
    with pytest.raises(ValueError, match="do not divide"):
        dot_product_attention(q, k, v, causal=True)
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, q, q, window=4)


@pytest.mark.parametrize("causal,offset,window", [
    (True, 0, 2048), (True, 0, 300), (True, 1024, 700), (True, 0, None),
    (False, 0, None)])
def test_block_bounds_are_each_others_inverse(causal, offset, window):
    """q-block i reads kv-block j exactly when kv-block j is read by
    q-block i, and a block is inside the bounds exactly when the mask
    keeps one of its pairs."""
    bq, bk, nq = 512, 256, 8
    nk = (nq * bq + offset) // bk
    g = dict(block_q=bq, block_k=bk, causal=causal, offset=offset,
             window=window)
    for qi in range(nq):
        lo, hi = _kv_bounds(qi, nk=nk, **g)
        for ki in range(nk):
            q_lo, q_hi = _q_bounds(ki, nq=nq, **g)
            assert (lo <= ki <= hi) == (q_lo <= qi <= q_hi)
            rows = np.arange(qi * bq, (qi + 1) * bq)[:, None] + offset
            keys = np.arange(ki * bk, (ki + 1) * bk)[None]
            keep = np.ones((bq, bk), bool)
            if causal:
                keep &= keys <= rows
            if window is not None:
                keep &= rows - keys < window
            assert (lo <= ki <= hi) == bool(keep.any()), (qi, ki)


def _attention_last_row(window, x):
    module = GatedGroupedAttention(n_head=4, n_kv_head=2, head_dim=8,
                                   window=window)
    variables = module.init(jax.random.PRNGKey(0), x)
    return module.apply(variables, x)[:, -1]


def test_rope_on_sliding_layers_only():
    """Without positions a causal row depends on WHICH tokens precede
    it, not on where they stand: shuffling the earlier tokens leaves a
    full layer's last row alone and moves a sliding layer's (window >=
    L, so both see every token)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 10, 16))
    shuffled = jnp.concatenate([x[:, 8::-1], x[:, 9:]], axis=1)
    np.testing.assert_allclose(_attention_last_row(None, x),
                               _attention_last_row(None, shuffled),
                               atol=1e-5, rtol=1e-5)
    assert not np.allclose(_attention_last_row(16, x),
                           _attention_last_row(16, shuffled), atol=1e-3)


# ------------------------------------------------------------------ #
# the expert layer                                                   #
# ------------------------------------------------------------------ #
def _layer(n_held, first_held, shared=True, seed=0):
    module = DroplessExperts(
        width=24, n_routed=16, n_held=n_held, first_held=first_held,
        top_k=3, route_scale=2.826, shared_width=24 if shared else 0)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 12, 32))
    variables = module.init(jax.random.PRNGKey(seed), x)
    return module, variables, x


def _reference_moe(variables, bias):
    p = variables["params"]
    return {"router": p["router"]["kernel"], "bias": bias,
            "experts": (p["w1"], p["w3"], p["w2"]),
            "shared": (tuple(p["shared"][k]["kernel"]
                             for k in ("w1", "w3", "w2"))
                       if "shared" in p else None)}


def test_the_shares_add_up_to_the_uncut_layer():
    """The 4 shares of one layer (4 experts each of 16), the shared
    expert counted once, sum to the reference's uncut layer; and the
    layer told it holds all 16 IS the uncut layer."""
    whole, variables, x = _layer(n_held=16, first_held=0)
    bias = jax.random.normal(jax.random.PRNGKey(7), (16,)) * 0.3
    variables = {**variables, "router_state": {"bias": bias}}
    m = x.reshape(-1, 32)
    config = dict(CONFIG, first_expert_held=0)
    with jax.default_matmul_precision("highest"):
        uncut, _ = ref.expert_layer(m, _reference_moe(variables, bias),
                                    config)
        shared_only = ref.swiglu(m, _reference_moe(variables,
                                                   bias)["shared"])
        np.testing.assert_allclose(
            whole.apply(variables, x).reshape(-1, 32), uncut,
            atol=2e-5, rtol=2e-5)
        total = jnp.zeros_like(m)
        for share in range(4):
            part = DroplessExperts(
                width=24, n_routed=16, n_held=4, first_held=4 * share,
                top_k=3, route_scale=2.826, shared_width=24)
            p = variables["params"]
            held = {**p, **{k: p[k][4 * share:4 * share + 4]
                            for k in ("w1", "w3", "w2")}}
            out = part.apply({**variables, "params": held}, x)
            total = total + out.reshape(-1, 32) - shared_only
        np.testing.assert_allclose(total + shared_only, uncut,
                                   atol=5e-5, rtol=5e-5)


def test_dropless_under_imbalance():
    """A bias that sends every token to held expert 5 (and most second
    choices to 6): every assignment is computed, none dropped, values
    and gradients as the reference's dense gather gives them."""
    module, variables, x = _layer(n_held=4, first_held=4)
    bias = jnp.zeros((16,)).at[5].set(10.0).at[6].set(1.0)
    variables = {**variables, "router_state": {"bias": bias}}
    config = dict(CONFIG, first_expert_held=4)

    def program(params, x):
        return module.apply({**variables, "params": params}, x)

    def reference(params, x):
        out, _ = ref.expert_layer(
            x.reshape(-1, 32),
            _reference_moe({"params": params}, bias), config)
        return out.reshape(x.shape)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(program(variables["params"], x),
                                   reference(variables["params"], x),
                                   atol=2e-5, rtol=2e-5)
        ct = jax.random.normal(jax.random.PRNGKey(3), x.shape)
        got = jax.grad(lambda p, x: jnp.sum(program(p, x) * ct), (0, 1))(
            variables["params"], x)
        want = jax.grad(lambda p, x: jnp.sum(reference(p, x) * ct), (0, 1))(
            variables["params"], x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)
    _, state = module.apply(variables, x, train=True,
                            mutable=["router_state", "counters"])
    counters = state["counters"]
    assert int(counters["moe_assignments"]) == 24 * 3
    assert int(counters["moe_expert_assignments"][1]) == 24   # expert 5
    assert int(counters["moe_assignments_dropped"]) == 0
    assert int(counters["moe_assignments_held"]) == int(
        counters["moe_expert_assignments"].sum()) >= 24


def test_bias_step_counts_in_bias_out_and_no_gradient():
    module, variables, x = _layer(n_held=16, first_held=0, shared=False)
    _, state = module.apply(variables, x, train=True,
                            mutable=["router_state", "counters"])
    counts = np.asarray(state["counters"]["moe_expert_assignments"])
    assert counts.sum() == 24 * 3 and counts.max() > counts.min()
    want = 0.001 * np.sign(counts.mean() - counts)
    np.testing.assert_allclose(state["router_state"]["bias"], want,
                               atol=1e-7)
    # an eval apply leaves both alone
    _, same = module.apply(variables, x, train=False,
                           mutable=["router_state", "counters"])
    assert not np.asarray(same["router_state"]["bias"]).any()
    assert int(same["counters"]["moe_bias_steps"]) == 0
    # no gradient reaches the bias
    grad = jax.grad(lambda b: jnp.sum(module.apply(
        {**variables, "router_state": {"bias": b}}, x)))(
        jnp.zeros((16,)))
    assert not np.asarray(grad).any()


def test_bias_moves_selection_and_not_the_weights():
    """Expert 9 scores low; a bias lifts it into every token's choice.
    Its weight is its own (unbiased) score over the chosen scores."""
    module, variables, x = _layer(n_held=16, first_held=0, shared=False)
    m = x.reshape(-1, 32)
    moe = _reference_moe(variables, jnp.zeros((16,)))
    lifted = dict(moe, bias=jnp.zeros((16,)).at[9].set(5.0))
    _, chosen_before = ref.route(m, moe, CONFIG)
    weights, chosen = ref.route(m, lifted, CONFIG)
    assert (np.asarray(chosen) == 9).any(-1).all()
    assert not (np.asarray(chosen_before) == 9).any(-1).all()
    scores = jax.nn.sigmoid(m @ moe["router"])
    picked = np.take_along_axis(np.asarray(scores), np.asarray(chosen), -1)
    np.testing.assert_allclose(
        weights, picked / picked.sum(-1, keepdims=True) * 2.826, rtol=1e-5)
    # and the program agrees with the reference under that bias
    out = module.apply({**variables,
                        "router_state": {"bias": lifted["bias"]}}, x)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(m, lifted,
                                   dict(CONFIG, first_expert_held=0))
    np.testing.assert_allclose(out.reshape(-1, 32), want, atol=2e-5,
                               rtol=2e-5)


def _poisoned(real):
    """What the chip's grouped matmul does to the rows past the groups:
    leaves them undefined. Here: NaN."""
    def grouped(x, w, sizes):
        out = real(x, w, sizes)
        filled = jnp.arange(out.shape[0])[:, None] < jnp.sum(sizes)
        return jnp.where(filled, out, jnp.nan)
    return grouped


@pytest.mark.parametrize("lifted", [None, 3])
def test_rows_no_assignment_fills_reach_nothing(monkeypatch, lifted):
    """256 tokens, 4 of 32 experts held, a balanced router and one with
    a bias on a held expert: the rows of the worst-case buffer that no
    assignment fills, NaN here as they may be on the chip, reach neither
    the output nor any gradient."""
    from analytics_zoo_tpu.keras.layers import moe

    monkeypatch.setattr(moe, "grouped_dot", _poisoned(moe.grouped_dot))
    module = DroplessExperts(width=16, n_routed=32, n_held=4, first_held=2,
                             top_k=4, route_scale=2.0, shared_width=16)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 16))
    variables = module.init(jax.random.PRNGKey(0), x)
    bias = jnp.zeros((32,))
    if lifted is not None:
        bias = bias.at[lifted].set(10.0)
    variables = {**variables, "router_state": {"bias": bias}}
    config = dict(CONFIG, first_expert_held=2, num_experts_per_tok=4,
                  route_scale=2.0)
    _, state = module.apply(variables, x, train=True,
                            mutable=["router_state", "counters"])
    held = int(state["counters"]["moe_assignments_held"])
    assert (held > 256) == (lifted is not None), held

    def program(params, x):
        return module.apply({**variables, "params": params}, x)

    def reference(params, x):
        out, _ = ref.expert_layer(
            x.reshape(-1, 16), _reference_moe({"params": params}, bias),
            config)
        return out.reshape(x.shape)

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(lambda p, x: jnp.sum(jnp.sin(
            program(p, x))), (0, 1))(variables["params"], x)
        want = jax.value_and_grad(lambda p, x: jnp.sum(jnp.sin(
            reference(p, x))), (0, 1))(variables["params"], x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


def test_grouped_dot_kernel_path_in_interpret_mode(monkeypatch):
    """The Pallas grouped matmul the chip runs (here interpreted),
    against ``ragged_dot``, over the rows the groups fill."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (256, 128))
    w = jax.random.normal(ks[1], (3, 128, 128))
    sizes = jnp.asarray([100, 0, 60], jnp.int32)
    got = gmm(x, w, sizes, jnp.float32, (128, 128, 128), None, None, False,
              True)
    want = grouped_dot(x, w, sizes)            # the CPU's ragged_dot
    np.testing.assert_allclose(got[:160], want[:160], atol=1e-3, rtol=1e-3)


# ------------------------------------------------------------------ #
# the model                                                          #
# ------------------------------------------------------------------ #
def test_model_matches_reference_in_float32():
    """Logits, loss and every gradient leaf, float32 compute."""
    config, model = _model()
    variables, x, y = _seeded(model)
    module = model.module
    with jax.default_matmul_precision("highest"):
        logits = module.apply(variables, {"input_ids": x})
        want = ref.forward(variables, {"input_ids": x}, config)
        assert logits.dtype == jnp.float32
        assert _rel(logits, want) < 2e-5
        loss, grads = jax.value_and_grad(lambda p: next_token_loss(
            module.apply({**variables, "params": p}, {"input_ids": x}),
            y))(variables["params"])
        ref_loss, ref_grads = ref.loss_and_grads(variables, x, y, config)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    got = jax.tree_util.tree_leaves_with_path(grads)
    wanted = jax.tree_util.tree_leaves(ref_grads)
    assert len(got) == len(wanted) > 40
    for (path, g), w in zip(got, wanted):
        assert _rel(g, w) < 2e-4, jax.tree_util.keystr(path)


def test_model_matches_reference_in_bfloat16():
    """bfloat16 matmuls and activations against the float32 reference:
    8 mantissa bits through three layers, and a router that may choose
    another expert where two scores are that close."""
    config, model = _model(dtype="bfloat16")
    variables, x, y = _seeded(model)
    logits = model.module.apply(variables, {"input_ids": x})
    want, routing = ref.forward(variables, {"input_ids": x}, config,
                                with_routing=True)
    assert logits.dtype == jnp.float32
    assert _rel(logits, want) < 0.03
    assert len(routing) == 2 and routing[0].shape == (2, 20, 3)
    loss = next_token_loss(logits, y)
    assert abs(float(loss) - float(ref.loss(variables, x, y, config))) < 0.02


def test_fit_updates_router_state_and_publishes_counters():
    """Through ``compile`` / ``fit`` / ``predict``: the bias and the
    counters ride ``_step_math`` like batch statistics, and the epoch's
    host sync publishes the counters' growth."""
    _, model = _model()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 64, (16, 17)).astype(np.int32)
    model.compile(optimizer=AdamWeightDecay(lr=1e-3))

    def published(name):
        family = get_registry().snapshot().get(f"zoo_model_{name}_total")
        return sum((family or {"values": {}})["values"].values())

    before = {n: published(n) for n in (
        "moe_assignments", "moe_assignments_dropped", "moe_bias_steps",
        "moe_expert_assignments", "moe_assignments_held")}
    history = model.fit(({"input_ids": ids[:, :-1]}, ids[:, 1:]),
                        batch_size=8, epochs=3)
    assert history[-1]["loss"] < history[0]["loss"]
    grown = {n: published(n) - v for n, v in before.items()}
    steps, layers = 6, 2
    assert grown["moe_assignments"] == steps * layers * 8 * 16 * 3
    assert grown["moe_bias_steps"] == steps * layers
    assert grown["moe_assignments_dropped"] == 0
    assert grown["moe_expert_assignments"] == grown["moe_assignments_held"]
    state = model.estimator.variables["router_state"]
    assert np.abs(np.asarray(state["layer_1"]["moe"]["bias"])).max() > 0
    logits = model.predict({"input_ids": ids[:8, :-1]}, batch_size=8)
    assert logits.shape == (8, 16, 64)
