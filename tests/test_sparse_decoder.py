"""The sparse decoder against its plain reference
(``benchmark/reference/trinity.py``) at tiny widths on the CPU: window
and full attention over grouped heads, the dropless expert layer and
its share of a deployment, the router's bias step, and the whole model
through ``Estimator``."""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.layers.byte_decoder import ByteDecoderLayer
from analytics_zoo_tpu.keras.layers.latent_decoder import LatentDecoderLayer
from analytics_zoo_tpu.keras.layers.moe import DroplessExperts, grouped_dot
from analytics_zoo_tpu.keras.layers.sparse_decoder import (
    FULL, SLIDING, GatedGroupedAttention, SparseDecoderLayer)
from analytics_zoo_tpu.learn.optim import AdamWeightDecay
from analytics_zoo_tpu.models.text import SparseDecoderLM
from analytics_zoo_tpu.models.text.sparse_decoder_lm import (
    KEPT_NAMES, ByteDecoderModule, LatentDecoderModule, SparseDecoderModule,
    _rematerialised, multi_byte_loss, next_token_loss)
from analytics_zoo_tpu.obs.metrics import get_registry
from analytics_zoo_tpu.ops import attention, pallas_attention
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


@pytest.mark.parametrize("path", ["fused", "split"])
@pytest.mark.parametrize("h,h_kv,lq,lk,window,blocks", [
    (4, 2, 384, 384, 200, (128, 128)),     # L no multiple of the window
    (4, 1, 256, 512, 130, (128, 256)),     # cross-length, one KV head
    (2, 2, 384, 384, None, (128, 128)),    # full, heads not grouped
    (8, 2, 256, 256, 128, (128, 128)),     # window = one block
    (8, 1, 512, 512, 200, (128, 128)),     # group 8; dQ over 3 kv-blocks
    (8, 1, 256, 512, 100, (128, 128)),     # oldest kv-blocks out of reach
])
def test_flash_window_and_grouped_heads_match_explicit_mask(
        monkeypatch, h, h_kv, lq, lk, window, blocks, path):
    """The owned kernels in interpret mode against ``reference_attention``
    with the mask written out: values and all three gradients, from the
    one backward kernel (dK/dV summed over the group's heads, dQ a head)
    and, the budget set to nothing, from the two that hold only blocks."""
    if path == "split":
        monkeypatch.setattr(pallas_attention, "FUSED_BWD_VMEM_BUDGET", 0)
    assert pallas_attention.flash_backward_path(
        lq, lk, 64, 64, 64, 4, window, *blocks) == path
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


@jax.custom_vjp
def _poison_back(x, filled):
    return x


_poison_back.defvjp(lambda x, filled: (x, filled),
                    lambda filled, g: (jnp.where(filled, g, jnp.nan), None))


def _poisoned(real):
    """What the chip's grouped matmul does with the rows past the
    groups: reads none of them, and leaves them undefined in its result
    and in its operand's gradient. Here: NaN."""
    def grouped(x, w, sizes):
        filled = jnp.arange(x.shape[0])[:, None] < jnp.sum(sizes)
        out = real(jnp.where(filled, _poison_back(x, filled), 0), w, sizes)
        return jnp.where(filled, out, jnp.nan)
    return grouped


def _small_tiles(monkeypatch, tile=16, block=8):
    """The bounded passes' tile and block cut down to the tests' sizes."""
    from analytics_zoo_tpu.keras.layers import moe

    monkeypatch.setattr(moe, "BUFFER_TILE", tile)
    monkeypatch.setattr(moe, "SEGMENT_BLOCK", block)
    return moe


@pytest.fixture
def unwritten_is_nan(monkeypatch):
    """Every buffer the bounded passes allocate starts as NaN, as an
    unwritten one may be on the chip. The passes are jitted: their
    traces from before are dropped first, and the poisoned ones after."""
    from analytics_zoo_tpu.keras.layers import moe

    jax.clear_caches()
    monkeypatch.setattr(
        moe, "_empty", lambda shape, dtype: jnp.full(shape, jnp.nan, dtype))
    yield
    jax.clear_caches()


@pytest.mark.parametrize("lifted", [None, 3])
def test_rows_no_assignment_fills_reach_nothing(monkeypatch,
                                                unwritten_is_nan, lifted):
    """256 tokens, 4 of 32 experts held, a balanced router and one with
    a bias on a held expert: the rows of the worst-case buffer that no
    assignment fills, NaN here as they may be on the chip, reach neither
    the output nor any gradient -- in the grouped products' results and
    in every buffer the bounded passes allocate (the sorted rows, the
    SwiGLU's, the token-order sums, and their gradients), which hold
    NaN past the last tile visited."""
    moe = _small_tiles(monkeypatch, tile=64, block=16)
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


# PR 27's full-buffer forms of the two row passes and its plan from a
# stable sort, kept as oracles for the bounded ones
def _gather_rows(x, plan):
    token_of_row, row_held, _, _ = plan
    return jnp.where(row_held[:, None], x[token_of_row], 0)


def _sum_slots(y, plan):
    _, _, row_of_slot, slot_held = plan
    return jnp.sum(jnp.where(slot_held[..., None], y[row_of_slot], 0),
                   axis=1)


def _sorted_plan(local, held_n, rows):
    n, k = local.shape
    flat = local.ravel()
    order = jnp.argsort(flat, stable=True)
    row_of_slot = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    first = order[:rows]
    return (first // k, flat[first] < held_n,
            jnp.minimum(row_of_slot, rows - 1).reshape(n, k),
            (flat < held_n).reshape(n, k))


# tokens, experts routed over, held (4..7), a token: a buffer of 192
# rows in tiles of 16
N_TOKENS, N_ROUTED, N_HELD, FIRST_HELD, PER_TOKEN = 48, 16, 4, 4, 4
HELD_CASES = pytest.mark.parametrize("held", [
    0,       # no trip: only the shared expert answers
    1,       # one row
    16,      # exactly one tile
    17,      # one past a tile
    24,      # the usual eighth of the buffer
    192,     # every token on four held experts: the whole buffer
])


def _choices(held, seed=0):
    """[n, k] expert ids, distinct in a row, ``held`` of them on the
    held experts, spread over the tokens at random."""
    rng = np.random.default_rng(seed)
    per_token = np.zeros(N_TOKENS, int)
    for _ in range(held):
        per_token[rng.choice(np.flatnonzero(per_token < N_HELD))] += 1
    inside = np.arange(FIRST_HELD, FIRST_HELD + N_HELD)
    outside = np.setdiff1d(np.arange(N_ROUTED), inside)
    return np.stack([rng.permutation(np.concatenate([
        rng.choice(inside, c, replace=False),
        rng.choice(outside, PER_TOKEN - c, replace=False)]))
        for c in per_token])


@HELD_CASES
def test_bounded_row_passes_match_the_full_buffer_ones(monkeypatch, held):
    """``_rows_out`` / ``_rows_back`` over a plan made without a sort
    against PR 27's gathers over its sorted plan: values, and the three
    gradients, on the rows that hold assignments."""
    moe = _small_tiles(monkeypatch)
    rows, d = N_TOKENS * N_HELD, 8
    local = jnp.asarray(_choices(held)) - FIRST_HELD
    local = jnp.where((local >= 0) & (local < N_HELD), local, N_HELD)
    sizes = jnp.sum(local.ravel()[:, None] == jnp.arange(N_HELD), 0)
    plan, oracle = moe._plan(local, sizes, rows=rows), _sorted_plan(
        local, N_HELD, rows)
    assert int(plan.held) == held
    np.testing.assert_array_equal(
        plan.slot_of_row[:held] // PER_TOKEN, oracle[0][:held])
    ks = jax.random.split(jax.random.PRNGKey(held), 4)
    x = jax.random.normal(ks[0], (N_TOKENS, d))
    y = jax.random.normal(ks[1], (rows, d))
    weights = jax.random.uniform(ks[2], (N_TOKENS, PER_TOKEN)) + 0.5
    row_held = oracle[1][:, None]

    def full_out(x):
        return _gather_rows(x, oracle)

    def full_back(y, weights):
        w_row = weights.ravel()[plan.slot_of_row][:, None]
        return _sum_slots(jnp.where(row_held, y, 0) * w_row, oracle)

    out, pull_out = jax.vjp(lambda x: moe._rows_out(x, plan), x)
    want_out, want_pull_out = jax.vjp(full_out, x)
    np.testing.assert_allclose(out[:held], want_out[:held], atol=1e-6)
    back, pull_back = jax.vjp(
        lambda y, w: moe._rows_back(y, w, plan), y, weights)
    want_back, want_pull_back = jax.vjp(full_back, y, weights)
    np.testing.assert_allclose(back, want_back, atol=1e-5, rtol=1e-5)
    # cotangents: what the rows past the last assignment hold reaches
    # nothing, so they are zeroed on both sides
    ct_rows = jnp.where(row_held, jax.random.normal(ks[3], (rows, d)), 0)
    ct_tokens = jax.random.normal(ks[3], (N_TOKENS, d))
    np.testing.assert_allclose(pull_out(ct_rows)[0],
                               want_pull_out(ct_rows)[0],
                               atol=1e-5, rtol=1e-5)
    dy, dw = pull_back(ct_tokens)
    want_dy, want_dw = want_pull_back(ct_tokens)
    np.testing.assert_allclose(dy[:held], want_dy[:held], atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(dw, want_dw, atol=1e-5, rtol=1e-5)


@HELD_CASES
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 3e-2)])
def test_layer_matches_per_token_reference_at_every_fill(
        monkeypatch, held, dtype, tol):
    """The layer's output and the gradients of ``x``, ``w1`` / ``w2`` /
    ``w3`` and the router against the plain reference (every expert on
    every token) while the buffer holds nothing, one row, a whole tile,
    one row more, its usual eighth, and everything; the tile is 16 rows
    of the buffer's 192, so the passes make 0 to 12 trips."""
    _small_tiles(monkeypatch)
    d = 32
    module = DroplessExperts(
        width=24, n_routed=N_ROUTED, n_held=N_HELD, first_held=FIRST_HELD,
        top_k=PER_TOKEN, route_scale=2.826, shared_width=24,
        dtype=jnp.dtype(dtype))
    chosen = _choices(held, seed=held)
    # the router reads each token's choices off its first 16 features
    marks = np.full((N_TOKENS, N_ROUTED), -3.0)
    np.put_along_axis(marks, chosen, 3.0, axis=1)
    rng = np.random.default_rng(held)
    x = rng.normal(0, 0.5, (1, N_TOKENS, d))
    x[0, :, :N_ROUTED] = marks + rng.normal(0, 0.3, marks.shape)
    # what the layer computes on: x in its compute dtype
    x = jnp.asarray(x, jnp.dtype(dtype)).astype(jnp.float32)
    variables = module.init(jax.random.PRNGKey(0), x)
    params = dict(variables["params"])
    params["router"] = {"kernel": jnp.eye(d, N_ROUTED)}
    bias = jnp.zeros((N_ROUTED,))
    config = dict(CONFIG, first_expert_held=FIRST_HELD,
                  num_experts_per_tok=PER_TOKEN)
    ct = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    variables = {**variables, "router_state": {"bias": bias}}

    def program(params, x):
        return module.apply({**variables, "params": params}, x)

    def reference(params, x):
        out, _ = ref.expert_layer(
            x.reshape(-1, d), _reference_moe({"params": params}, bias),
            config)
        return out.reshape(x.shape)

    _, state = module.apply({**variables, "params": params}, x, train=True,
                            mutable=["router_state", "counters"])
    counters = state["counters"]
    assert int(counters["moe_assignments_held"]) == held
    assert int(counters["moe_buffer_tiles_visited"]) == -(-held // 16)
    assert int(counters["moe_buffer_tiles"]) == 12
    with jax.default_matmul_precision("highest"):
        # a sum of squares: a random cotangent would make each weight's
        # gradient one cancelling bfloat16 dot product
        got = jax.value_and_grad(
            lambda p, x: jnp.sum(program(p, x) ** 2) / 2, (0, 1))(params, x)
        want = jax.value_and_grad(
            lambda p, x: jnp.sum(reference(p, x) ** 2) / 2, (0, 1))(
                params, x)
        np.testing.assert_allclose(
            program(params, x), reference(params, x), atol=20 * tol,
            rtol=20 * tol)
    got = jax.tree_util.tree_leaves_with_path(got)
    want = jax.tree_util.tree_leaves(want)
    assert len(got) == len(want) == 9   # the sum, 7 parameters, x
    for (path, g), w in zip(got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all(), jax.tree_util.keystr(path)
        assert np.linalg.norm(g - w) <= tol * np.linalg.norm(w) + 1e-6, (
            jax.tree_util.keystr(path))


def _not_in_a_loop(jaxpr, inside=False):
    """(equation, under a ``while`` or a Pallas call?) for every
    equation of ``jaxpr`` and of what it calls."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        here = inside or eqn.primitive.name in ("while", "pallas_call")
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _not_in_a_loop(sub, here)


def test_no_pass_over_the_whole_buffer_outside_a_loop(monkeypatch):
    """The jaxpr of the layer's ``value_and_grad``: no gather, select,
    multiply or add makes buffer-many (256 here, 65,536 at the published
    widths) rows of the model's or the experts' width outside a
    ``while`` body or a Pallas call; and every loop's trip count is
    computed from the router's choice (the per-expert ``sizes``), not a
    constant."""
    _small_tiles(monkeypatch)
    module = DroplessExperts(width=24, n_routed=16, n_held=4, first_held=4,
                             top_k=4, route_scale=2.0, shared_width=24)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 32))
    variables = module.init(jax.random.PRNGKey(0), x)
    rows = 64 * 4

    def loss(params, x):
        return jnp.sum(jnp.sin(module.apply({**variables, "params": params},
                                            x)))

    closed = jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(
        variables["params"], x)
    loops, whole = [], []
    for eqn, inside in _not_in_a_loop(closed.jaxpr):
        if eqn.primitive.name == "while":
            loops.append(eqn)
        wide = [v.aval.shape for v in eqn.outvars
                if len(v.aval.shape) == 2 and v.aval.shape[0] >= rows
                and v.aval.shape[1] >= 24]
        if wide and not inside and eqn.primitive.name in (
                "gather", "select_n", "mul", "add", "scatter-add",
                "scatter", "logistic", "broadcast_in_dim"):
            whole.append((eqn.primitive.name, wide))
    assert not whole, whole
    # dispatch, SwiGLU, combine: forward and backward
    assert len(loops) == 6, len(loops)
    for eqn in loops:
        # fori_loop's carry is (i, upper, value): upper must be computed
        n_consts = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
        upper = eqn.invars[n_consts + 1]
        assert not isinstance(upper, jax.extend.core.Literal)

    # ... from the routing: with the choices cut out of the program (an
    # eval on fixed counts) the same loops' bounds would be constants,
    # so check the dependence by value -- more held, more trips
    def trips(bias):
        _, state = module.apply(
            {**variables, "router_state": {"bias": bias}}, x, train=True,
            mutable=["router_state", "counters"])
        return int(state["counters"]["moe_buffer_tiles_visited"])

    few = trips(jnp.zeros((16,)).at[4:8].set(-10.0))
    many = trips(jnp.zeros((16,)).at[4:8].set(10.0))
    assert (few, many) == (0, 16)


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


# ------------------------------------------------------------------ #
# what a rematerialised layer keeps                                  #
# ------------------------------------------------------------------ #
KINDS = pytest.mark.parametrize("kind", [SLIDING, FULL])


@pytest.fixture
def on_the_chip(monkeypatch):
    """The dispatcher told it is on the chip, as
    ``test_step_scopes.test_attention_path_names_itself`` does; the
    kernels it then picks run here in interpret mode."""
    monkeypatch.setattr(attention, "_platform", lambda q: "tpu")


def _one_layer(kind):
    """A decoder with one dense layer of ``kind`` (a sparse decoder's
    window or full kind, ``"latent"`` or ``"byte"``) at a length the
    flash path takes, its parameters, and a loss of them."""
    common = dict(vocab=64, dense_width=96)
    sparse = dict(hidden_size=64, n_dense_layers=1, n_head=2, expert_width=16,
                  n_routed=8, n_held=4)
    length, loss_of = 1024, next_token_loss
    if kind == "latent":
        module = LatentDecoderModule(
            **common, **sparse, n_layers=1, nope_dim=64, rope_dim=64,
            v_dim=64, latent_dim=32)
    elif kind == "byte":
        # three windows of 1,024 with 128 summaries each
        module = ByteDecoderModule(
            **common, hidden_size=128, n_layers=1, n_head=2, head_dim=64,
            window=1024, chunk=8, n_pred_heads=2)
        length, loss_of = 3072, multi_byte_loss
    else:
        module = SparseDecoderModule(
            **common, **sparse, layer_types=(kind,), n_kv_head=1,
            head_dim=64, window=256)
    ids = np.random.default_rng(0).integers(0, 64, (1, length)).astype(
        np.int32)
    params = module.init(jax.random.PRNGKey(0), ids)["params"]

    def loss(params):
        logits = module.apply({"params": params}, ids)
        return loss_of(logits, jnp.roll(ids, -1, 1)), logits

    return params, loss


def _without_policy(monkeypatch):
    """``nn.remat`` as it stood before the policy: the layer's input is
    all the backward pass keeps."""
    remat = nn.remat
    monkeypatch.setattr(
        nn, "remat", lambda target, policy, **kwargs: remat(target, **kwargs))


@KINDS
def test_remat_layer_backward_holds_two_flash_kernels(
        monkeypatch, on_the_chip, kind):
    """Forward + logsumexp and the one backward kernel: the layer's
    second forward holds no attention kernel, because both results of
    the first are kept. Without the policy it holds a third; on the path
    that holds [L, L] scores nothing carries the flash names (the
    operands' and SwiGLU's, which are O(L), are carried there too)."""
    params, loss = _one_layer(kind)

    def text():
        return str(jax.make_jaxpr(jax.grad(loss, has_aux=True))(params))

    assert text().count("pallas_call[") == 2
    _without_policy(monkeypatch)
    assert text().count("pallas_call[") == 3
    monkeypatch.undo()      # the policy again, and the dispatcher's own eyes
    monkeypatch.setattr(attention, "_platform", lambda q: "cpu")
    scores = text()
    assert "pallas_call[" not in scores and "name=flash_" not in scores
    assert "name=attention_q]" in scores and "name=swiglu_up]" in scores


REMAT_KINDS = pytest.mark.parametrize(
    "kind", [SLIDING, FULL, "latent", "byte"])
_EPS32 = float(np.finfo(np.float32).eps)


def _kept_and_whole(monkeypatch, kind, run):
    """``run(value_and_grad of the loss, parameters)`` of one layer of
    ``kind`` under the policy, and of the same layer rematerialised
    whole: two ((loss, logits), gradients)."""
    def once():
        params, loss = _one_layer(kind)
        return run(jax.value_and_grad(loss, has_aux=True), params)

    kept = once()
    _without_policy(monkeypatch)
    return kept, once()


def _assert_same(kept, whole, eps=0):
    """Loss, logits and every gradient leaf, none of them all zero:
    equal to the bit, or within ``eps`` float32 roundings of the leaf's
    largest magnitude."""
    kept, whole = (dict(jax.tree_util.tree_leaves_with_path(side))
                   for side in (kept, whole))
    assert kept.keys() == whole.keys()
    for path, want in whole.items():
        largest = float(jnp.max(jnp.abs(want)))
        assert largest > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            kept[path], want, rtol=0, atol=eps * _EPS32 * largest,
            err_msg=jax.tree_util.keystr(path))


@REMAT_KINDS
def test_remat_policy_changes_no_bit(monkeypatch, on_the_chip, kind):
    """The kept values are what the second forward would produce again:
    compiled as one program in float32, loss, logits and every gradient
    leaf equal those of the layer rematerialised whole, to the bit for
    the full, latent and byte kinds.

    The sliding kind's two compiled programs differ already in their
    *first* forward (PR 34): keeping ``attention_q`` makes XLA:CPU cut
    QK-norm + RoPE + transpose into other fusions (the rotated half
    arrives from a fusion of its own), the HLO arithmetic is the same
    ``a * b + c * d``, and LLVM contracts it into another fused
    multiply-add. Measured here: logits within 3 roundings of the
    largest logit, gradients within 5 of each leaf's largest entry;
    held to 8. No other name does it, and with the backend's
    optimisations off the sliding kind's programs agree to the bit too
    (``test_remat_policy_changes_no_bit_before_llvm``)."""
    kept, whole = _kept_and_whole(
        monkeypatch, kind, lambda grad, params: jax.jit(grad)(params))
    _assert_same(kept, whole, eps=8 if kind == SLIDING else 0)


def test_remat_policy_changes_no_bit_before_llvm(monkeypatch, on_the_chip):
    """The sliding kind compiled with XLA:CPU's backend optimisations
    off (no contraction into fused multiply-adds): equal to the bit, so
    what the compiled programs differ by is the compiler's arithmetic
    and not a kept value."""
    def run(grad, params):
        return jax.jit(grad).lower(params).compile(
            compiler_options={"xla_backend_optimization_level": 0})(params)

    _assert_same(*_kept_and_whole(monkeypatch, SLIDING, run))


@REMAT_KINDS
def test_remat_policy_changes_no_bit_op_by_op(monkeypatch, on_the_chip, kind):
    """The same comparison with every operation evaluated on its own,
    so no compiler's fusion stands between a kept value and its
    recomputation: equal to the bit for every kind."""
    def run(grad, params):
        with jax.disable_jit():
            return grad(params)

    _assert_same(*_kept_and_whole(monkeypatch, kind, run))


_EXPERTS = dict(width=16, n_routed=8, n_held=4, top_k=2, shared_width=32)
_GATED = dict(n_head=2, n_kv_head=1, head_dim=64, window=256, dense_width=96)
_BF16, _F32 = jnp.bfloat16, jnp.float32
# the gated attention branch and the MLP branch of a sparse decoder layer
_GATED_KEPT = {
    "attention_q_proj": (1, 1024, 128), "attention_gate": (1, 1024, 128),
    "attention_q": (1, 2, 1024, 64), "attention_k": (1, 1, 1024, 64),
    "attention_v": (1, 1, 1024, 64), "flash_attention_out": (1, 2, 1024, 64),
    "flash_attention_lse": (2, 1024, 128), "attention_out": (1, 1024, 64),
    "mlp_out": (1, 1024, 64)}
# layer class, its arguments, width d, length L, and every value the
# backward pass finds kept beside the layer's input: name -> shape
# (bfloat16 as the forward makes them; the logsumexp float32)
KEPT = {
    "sliding": (SparseDecoderLayer, dict(kind=SLIDING, **_GATED), 64, 1024, {
        **_GATED_KEPT,
        "swiglu_gate": (1, 1024, 96), "swiglu_up": (1, 1024, 96)}),
    # the shared expert's SwiGLU (two experts' width in one); nothing
    # under moe_route / moe_dispatch / moe_experts / moe_combine
    "full_experts": (SparseDecoderLayer,
                     dict(kind=FULL, **_GATED, experts=_EXPERTS), 64, 1024, {
        **_GATED_KEPT,
        "swiglu_gate": (1024, 32), "swiglu_up": (1024, 32)}),
    "latent": (LatentDecoderLayer, dict(
        attention=dict(n_head=2, nope_dim=64, rope_dim=64, v_dim=64,
                       latent_dim=32), dense_width=96), 64, 1024, {
        "attention_q": (1, 2, 1024, 128), "attention_k": (1, 2, 1024, 64),
        "attention_k_rot": (1, 1, 1024, 64), "attention_v": (1, 2, 1024, 64),
        "flash_attention_out": (1, 2, 1024, 64),
        "flash_attention_lse": (2, 1024, 128), "attention_out": (1, 1024, 64),
        "swiglu_gate": (1, 1024, 96), "swiglu_up": (1, 1024, 96)}),
    "byte": (ByteDecoderLayer, dict(
        attention=dict(n_head=2, head_dim=64, window=1024, chunk=8),
        dense_width=96), 128, 3072, {
        "attention_q": (1, 2, 3072, 64), "attention_k": (1, 2, 3072, 64),
        "attention_v": (1, 2, 3072, 64),
        "flash_attention_out": (1, 2, 3072, 64),
        "flash_attention_lse": (2, 3072, 128), "attention_out": (1, 3072, 128),
        "eva_k_summary": (1, 2, 384, 64), "eva_v_summary": (1, 2, 384, 64),
        "mlp_in": (1, 3072, 128),
        "swiglu_gate": (1, 3072, 96), "swiglu_up": (1, 3072, 96)}),
}


@pytest.mark.parametrize("layer", sorted(KEPT))
def test_rematerialised_layer_keeps_exactly_the_named_values(
        on_the_chip, capsys, layer):
    """One layer of each type under ``_rematerialised``, traced in
    bfloat16 on the flash path: the names it carries are the intended
    ones at the intended shapes, in the dtype the forward made them, all
    of them are in the policy's one tuple, and the residuals of its
    gradient are the layer's arguments and those values, no other."""
    cls, kwargs, d, length, want = KEPT[layer]
    module = _rematerialised(cls)(**kwargs, dtype=_BF16)
    h = jax.ShapeDtypeStruct((1, length, d), _BF16)
    variables = jax.eval_shape(
        lambda key, x: module.init(key, x, False), jax.random.PRNGKey(0), h)
    params = variables.pop("params")

    def scalar(params, h):
        return jnp.sum(module.apply({"params": params, **variables}, h,
                                    False).astype(_F32))

    want = {name: ("f32" if name.endswith("_lse") else "bf16")
            + str(list(shape)).replace(" ", "")
            for name, shape in want.items()}
    # every ``name`` equation of the gradient, as the jaxpr prints it
    # (the kernel's two are named in its custom_vjp's forward rule)
    named = {name: value for value, name in re.findall(
        r":(\w+\[[\d,]*\]) = name\[name=(\w+)\]",
        str(jax.make_jaxpr(jax.grad(scalar))(params, h)))}
    assert named == want
    assert set(named) <= set(KEPT_NAMES)
    jax.ad_checkpoint.print_saved_residuals(scalar, params, h)
    kept = sorted(line.split()[0]
                  for line in capsys.readouterr().out.splitlines()
                  if "from the argument" not in line
                  and "from a constant" not in line)    # RoPE's frequencies
    assert kept == sorted(want.values())


@pytest.mark.parametrize("window", [256, None])
def test_names_outside_a_policy_change_no_program(monkeypatch, window):
    """A differentiated flash call under no ``jax.checkpoint`` lowers
    to the same program with the names and without them, and keeps
    for its backward what it kept: q, k, v, the output and the
    logsumexp as the kernel writes it."""
    q = jax.ShapeDtypeStruct((1, 2, 1024, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1, 1024, 64), jnp.bfloat16)

    def attend(q, k, v):
        return jnp.sum(pallas_flash_attention_fwd(
            q, k, v, True, None, None, None, window).astype(jnp.float32))

    def lowered():
        return jax.jit(jax.grad(attend, (0, 1, 2))).trace(q, kv, kv).lower(
            lowering_platforms=("tpu",)).as_text()

    kept = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda *args: jax.vjp(attend, *args)[1], q, kv, kv))
    assert [(k.shape, k.dtype) for k in kept] == [
        (q.shape, q.dtype), (kv.shape, kv.dtype), (kv.shape, kv.dtype),
        (q.shape, q.dtype), ((2, 1024, 128), jnp.float32)]
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    texts = []
    for name in (pallas_attention.checkpoint_name, lambda x, name: x):
        monkeypatch.setattr(pallas_attention, "checkpoint_name", name)
        texts.append(lowered())     # one call site: kernels carry theirs
    named, bare = texts
    assert named.count("stablehlo.custom_call @tpu_custom_call") == 2
    assert named == bare


@pytest.mark.parametrize("which", ["swiglu", "gated", "latent", "eva"])
def test_layer_names_outside_a_policy_change_no_program(monkeypatch, which):
    """The values a layer names: a differentiated ``SwiGLU`` and each
    attention module under no ``jax.checkpoint`` (``MoEFFN``'s users,
    serving, a model without ``nn.remat``) lower to the same StableHLO
    with the names and without them."""
    from analytics_zoo_tpu.keras.layers import (
        byte_decoder, latent_decoder, moe, sparse_decoder)

    module, files, shape = {
        "swiglu": (moe.SwiGLU(96, dtype=_BF16), (moe,), (1, 64, 32)),
        "gated": (GatedGroupedAttention(2, 1, 16, window=8, dtype=_BF16),
                  (sparse_decoder,), (1, 64, 32)),
        "latent": (latent_decoder.LatentAttention(
            n_head=2, nope_dim=16, rope_dim=8, v_dim=16, latent_dim=8,
            dtype=_BF16), (latent_decoder, sparse_decoder), (1, 64, 32)),
        "eva": (byte_decoder.EvaAttention(
            n_head=2, head_dim=16, window=16, chunk=4, dtype=_BF16),
            (byte_decoder, sparse_decoder), (1, 64, 32)),
    }[which]
    x = jax.ShapeDtypeStruct(shape, _BF16)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)

    def traced():
        def scalar(params, x):      # anew: a trace is cached by function
            return jnp.sum(module.apply(params, x).astype(_F32))

        return jax.jit(jax.grad(scalar, (0, 1))).trace(params, x)

    named = traced()
    assert "= name[" in str(named.jaxpr)
    for file in files:
        monkeypatch.setattr(file, "checkpoint_name", lambda x, name: x)
    bare = traced()
    assert "= name[" not in str(bare.jaxpr)

    def text(traced):
        # private functions are numbered in the order they were traced
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", traced.lower().as_text())

    assert text(bare) == text(named)


def test_fit_updates_router_state_and_publishes_counters(monkeypatch,
                                                         compiled_anew):
    """Through ``compile`` / ``fit`` / ``predict``: the bias and the
    counters ride ``_step_math`` like batch statistics, and the epoch's
    host sync publishes the counters' growth; the buffer of 384 rows a
    layer is cut into 6 tiles here."""
    _small_tiles(monkeypatch, tile=64, block=16)
    _, model = _model()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 64, (16, 17)).astype(np.int32)
    model.compile(optimizer=AdamWeightDecay(lr=1e-3))

    def published(name):
        family = get_registry().snapshot().get(f"zoo_model_{name}_total")
        return sum((family or {"values": {}})["values"].values())

    before = {n: published(n) for n in (
        "moe_assignments", "moe_assignments_dropped", "moe_bias_steps",
        "moe_expert_assignments", "moe_assignments_held",
        "moe_buffer_tiles_visited", "moe_buffer_tiles")}
    history = model.fit(({"input_ids": ids[:, :-1]}, ids[:, 1:]),
                        batch_size=8, epochs=3)
    assert history[-1]["loss"] < history[0]["loss"]
    grown = {n: published(n) - v for n, v in before.items()}
    steps, layers = 6, 2
    assert grown["moe_assignments"] == steps * layers * 8 * 16 * 3
    assert grown["moe_bias_steps"] == steps * layers
    assert grown["moe_assignments_dropped"] == 0
    assert grown["moe_expert_assignments"] == grown["moe_assignments_held"]
    # one layer-step's worth a step: 6 tiles, and as many visited as
    # the step's held assignments reach into
    assert grown["moe_buffer_tiles"] == steps * layers * 6
    assert (steps * layers <= grown["moe_buffer_tiles_visited"]
            <= grown["moe_assignments_held"] // 64 + steps * layers
            < grown["moe_buffer_tiles"])
    state = model.estimator.variables["router_state"]
    assert np.abs(np.asarray(state["layer_1"]["moe"]["bias"])).max() > 0
    logits = model.predict({"input_ids": ids[:8, :-1]}, batch_size=8)
    assert logits.shape == (8, 16, 64)
