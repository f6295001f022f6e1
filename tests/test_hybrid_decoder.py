"""The hybrid decoder (Qwen3-Next) against its plain reference
(``benchmark/reference/qwen3_next.py``) at tiny widths on the CPU: one
period of four layers (three Gated DeltaNet, one gated attention),
gated attention with partial rotary, the softmax router with its
renormalised weights and balance loss, the expert layer's shares with
the gated shared expert, and the whole model through ``Estimator``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.layers.hybrid_decoder import LINEAR
from analytics_zoo_tpu.keras.layers.moe import DroplessExperts
from analytics_zoo_tpu.keras.layers.sparse_decoder import (
    FULL, GatedGroupedAttention)
from analytics_zoo_tpu.learn.optim import AdamWeightDecay
from analytics_zoo_tpu.models.text import HybridDecoderLM
from analytics_zoo_tpu.models.text.hybrid_decoder_lm import layer_kinds
from analytics_zoo_tpu.models.text.sparse_decoder_lm import next_token_loss
from analytics_zoo_tpu.obs.metrics import get_registry
from benchmark.reference import qwen3_next as ref

CONFIG = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=1e7,
    rms_norm_eps=1e-6, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, full_attention_interval=4,
    num_experts_per_tok=3, norm_topk_prob=True, num_experts=4,
    num_experts_routed_over=16, first_expert_held=4,
    moe_intermediate_size=12, shared_expert_intermediate_size=12,
    router_aux_loss_coef=0.001, vocab_size=64, num_hidden_layers=4)


def _model(dtype="float32", **changes):
    c = {**CONFIG, **changes}
    return c, HybridDecoderLM(
        vocab=c["vocab_size"], hidden_size=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        full_attention_interval=c["full_attention_interval"],
        n_head=c["num_attention_heads"],
        n_kv_head=c["num_key_value_heads"], head_dim=c["head_dim"],
        partial_rotary_factor=c["partial_rotary_factor"],
        n_k_head=c["linear_num_key_heads"],
        n_v_head=c["linear_num_value_heads"],
        head_k_dim=c["linear_key_head_dim"],
        head_v_dim=c["linear_value_head_dim"],
        expert_width=c["moe_intermediate_size"],
        n_routed=c["num_experts_routed_over"], n_held=c["num_experts"],
        first_held=c["first_expert_held"], top_k=c["num_experts_per_tok"],
        shared_width=c["shared_expert_intermediate_size"],
        aux_loss_coef=c["router_aux_loss_coef"],
        conv_width=c["linear_conv_kernel_dim"], chunk=8,
        rope_theta=c["rope_theta"], eps=c["rms_norm_eps"], dtype=dtype)


def _seeded(model, seed=0, length=32, rows=2):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, model._config["vocab"], (rows, length + 1))
    x, y = ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)
    variables = model.estimator.adapter.init(jax.random.PRNGKey(seed),
                                             {"input_ids": x})
    return variables, x, y


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def _program_loss(module, variables, x, y):
    """What ``Estimator._step_math`` minimises: the next-token loss and
    every sown ``losses`` leaf."""
    def of(params):
        logits, state = module.apply(
            {**variables, "params": params}, {"input_ids": x}, train=True,
            mutable=["losses", "counters", "router_state"])
        return next_token_loss(logits, y) + sum(
            jnp.sum(a) for a in jax.tree_util.tree_leaves(state["losses"]))
    return of


# ------------------------------------------------------------------ #
# the model against the reference                                    #
# ------------------------------------------------------------------ #
def test_one_period_is_three_linear_layers_and_one_attention():
    assert layer_kinds(4, 4) == (LINEAR, LINEAR, LINEAR, FULL)
    assert layer_kinds(8, 4).count(FULL) == 2
    _, model = _model()
    variables, _, _ = _seeded(model)
    p = variables["params"]
    for i in range(3):
        assert set(p[f"layer_{i}"]) == {"input_norm", "linear_mixer",
                                        "pre_mlp_norm", "moe"}
        assert set(p[f"layer_{i}"]["linear_mixer"]) == {
            "in_proj_qkvz", "in_proj_ba", "conv", "A_log", "dt_bias",
            "norm", "out_proj"}
    assert set(p["layer_3"]) == {"input_norm", "attention", "pre_mlp_norm",
                                 "moe"}
    assert p["layer_0"]["linear_mixer"]["in_proj_qkvz"]["kernel"].shape == (
        32, 2 * 16 + 2 * 32)
    assert p["layer_0"]["linear_mixer"]["conv"].shape == (4, 2 * 16 + 32)
    assert p["layer_3"]["moe"]["shared_gate"]["kernel"].shape == (32, 1)
    # zero-centred norms start at 0, the gated norm at 1
    assert float(jnp.abs(p["layer_0"]["input_norm"]["scale"]).max()) == 0
    assert float(jnp.abs(p["final_norm"]["scale"]).max()) == 0
    assert float(jnp.abs(p["layer_3"]["attention"]["q_norm"]["scale"]).max(
    )) == 0
    np.testing.assert_array_equal(p["layer_0"]["linear_mixer"]["norm"], 1.0)
    # the router state is declared and stays at zero
    assert set(variables["router_state"]) == {f"layer_{i}" for i in range(4)}


def test_model_matches_reference_in_float32():
    """Logits, the loss with the balance term, and every gradient leaf,
    float32 compute. The logits agree to 1e-5; the gradients to 3e-5
    over the whole tree and 2e-4 a leaf: float32 rounding through the
    Gated DeltaNet layers' exponential gates (the same model with the
    sequential recurrence in place of the chunked one reads the same)."""
    config, model = _model()
    variables, x, y = _seeded(model)
    module = model.module
    with jax.default_matmul_precision("highest"):
        logits = module.apply(variables, {"input_ids": x})
        want = ref.forward(variables, {"input_ids": x}, config)
        assert logits.dtype == jnp.float32
        assert _rel(logits, want) < 1e-5
        loss, grads = jax.value_and_grad(
            _program_loss(module, variables, x, y))(variables["params"])
        ref_loss, ref_grads = ref.loss_and_grads(variables, x, y, config)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    got = jax.tree_util.tree_leaves_with_path(grads)
    wanted = jax.tree_util.tree_leaves(ref_grads)
    assert len(got) == len(wanted) > 40
    for (path, g), w in zip(got, wanted):
        assert _rel(g, w) < 2e-4, jax.tree_util.keystr(path)
    assert _rel(jnp.concatenate([g.ravel() for _, g in got]),
                jnp.concatenate([w.ravel() for w in wanted])) < 3e-5


def test_model_matches_reference_in_bfloat16():
    """bfloat16 matmuls and activations against the float32 reference:
    no further off than the reference itself with bfloat16-rounded
    operands (at these widths both read ~0.1: every Gated DeltaNet
    layer roughly doubles the error, 0.017 after one)."""
    config, model = _model(dtype="bfloat16")
    variables, x, y = _seeded(model)
    logits = model.module.apply(variables, {"input_ids": x})
    want, routing = ref.forward(variables, {"input_ids": x}, config,
                                with_routing=True)
    with ref.operands_rounded_to(jnp.bfloat16):
        rounding = _rel(ref.forward(variables, {"input_ids": x}, config),
                        want)
    assert logits.dtype == jnp.float32
    assert rounding < 0.2
    assert _rel(logits, want) < 1.25 * rounding
    assert len(routing) == 4 and routing[0].shape == (2, 32, 3)


def test_balance_loss_reaches_the_gradient():
    """The sown term is the reference's and moves the router."""
    config, model = _model()
    variables, x, y = _seeded(model)
    _, state = model.module.apply(variables, {"input_ids": x}, train=True,
                                  mutable=["losses", "counters",
                                           "router_state"])
    sown = jax.tree_util.tree_leaves(state["losses"])
    assert len(sown) == 4
    with jax.default_matmul_precision("highest"):
        with_aux = ref.loss(variables, x, y, config)
        without = ref.loss(variables, x, y,
                           dict(config, router_aux_loss_coef=0.0))
    np.testing.assert_allclose(sum(float(a) for a in sown),
                               float(with_aux - without), rtol=1e-4)
    # at balance the term reads top_k: 3 x coef
    assert 0.5 * 3e-3 < sum(float(a) for a in sown) < 3 * 3e-3


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_moves_the_reference(fault):
    """What the cell's tolerance is set against: every listed mistake
    changes the logits by far more than float32 rounding."""
    config, model = _model()
    variables, x, _ = _seeded(model)
    want = ref.forward(variables, {"input_ids": x}, config)
    with ref.faulty(fault):
        wrong = ref.forward(variables, {"input_ids": x}, config)
    assert _rel(wrong, want) > 1e-3, fault


def test_unknown_fault_is_refused():
    with pytest.raises(ValueError, match="unknown fault"):
        with ref.faulty("no_such_fault"):
            pass


# ------------------------------------------------------------------ #
# gated attention with partial rotary                                #
# ------------------------------------------------------------------ #
def test_gated_attention_ropes_the_leading_dims_only():
    """``rope_dims`` 4 of 16 with zero-centred QK-norms against the
    reference's gated attention; the rotary dims carry positions and
    the rest do not."""
    config = dict(CONFIG, partial_rotary_factor=0.25)
    attn = GatedGroupedAttention(n_head=4, n_kv_head=2, head_dim=16,
                                 rope_dims=4, rope_theta=1e7, eps=1e-6,
                                 unit_offset=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    variables = attn.init(jax.random.PRNGKey(1), x)
    p = jax.tree_util.tree_map(lambda a: a + 0.1, variables["params"])
    weights = {**{k: p[k]["kernel"] for k in ("q", "k", "v", "gate", "out")},
               "q_norm": p["q_norm"]["scale"], "k_norm": p["k_norm"]["scale"]}
    with jax.default_matmul_precision("highest"):
        got = attn.apply({"params": p}, x)
        want = ref.gated_attention(x, weights, config)
        assert _rel(got, want) < 1e-5
        with ref.faulty("rope_all_dims"):
            assert _rel(ref.gated_attention(x, weights, config), want) > 1e-3
        whole = GatedGroupedAttention(n_head=4, n_kv_head=2, head_dim=16,
                                      rope_dims=16, rope_theta=1e7,
                                      eps=1e-6, unit_offset=True)
        assert _rel(whole.apply({"params": p}, x), want) > 1e-3


def test_gated_attention_defaults_are_trinity_minis():
    """No ``rope_dims``: RoPE on every dim of a sliding layer, none on a
    full one; the QK-norms start at 1 and scale by ``w``."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    full = GatedGroupedAttention(n_head=4, n_kv_head=2, head_dim=8)
    variables = full.init(jax.random.PRNGKey(1), x)
    np.testing.assert_array_equal(variables["params"]["q_norm"]["scale"], 1)
    zero_centred = GatedGroupedAttention(n_head=4, n_kv_head=2, head_dim=8,
                                         unit_offset=True)
    shifted = jax.tree_util.tree_map(lambda a: a, variables)
    for norm in ("q_norm", "k_norm"):
        shifted["params"][norm]["scale"] = (
            variables["params"][norm]["scale"] - 1.0)
    np.testing.assert_allclose(zero_centred.apply(shifted, x),
                               full.apply(variables, x), rtol=1e-5,
                               atol=1e-6)


# ------------------------------------------------------------------ #
# the softmax router and the expert layer's shares                   #
# ------------------------------------------------------------------ #
def _experts(n_held=16, first_held=0, aux=0.01):
    return DroplessExperts(width=12, n_routed=16, n_held=n_held,
                           first_held=first_held, top_k=3, shared_width=12,
                           bias_step=0.0, score="softmax", shared_gate=True,
                           aux_loss_coef=aux)


def test_softmax_router_weights_and_balance_loss_by_hand():
    """All 16 experts held: each token's output is its three most
    probable experts' SwiGLUs under their renormalised probabilities,
    plus the gated shared expert; the sown loss is E sum f P."""
    layer = _experts()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    variables = {k: v for k, v in layer.init(jax.random.PRNGKey(1), x).items()
                 if k != "losses"}        # what the Estimator keeps
    p = jax.tree_util.tree_map(np.asarray, variables["params"])
    with jax.default_matmul_precision("highest"):
        out, state = layer.apply(variables, x, train=True,
                                 mutable=["losses", "counters",
                                          "router_state"])
    m = np.asarray(x, np.float64).reshape(-1, 32)
    logits = m @ p["router"]["kernel"]
    prob = np.exp(logits - logits.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    top = np.argsort(-prob, axis=-1)[:, :3]

    def silu(a):
        return a / (1 + np.exp(-a))

    want = np.zeros_like(m)
    for t in range(m.shape[0]):
        chosen = prob[t, top[t]] / prob[t, top[t]].sum()
        for e, w in zip(top[t], chosen):
            h = silu(m[t] @ p["w1"][e]) * (m[t] @ p["w3"][e])
            want[t] += w * (h @ p["w2"][e])
        s = p["shared"]
        shared = (silu(m[t] @ s["w1"]["kernel"]) * (m[t] @ s["w3"]["kernel"])
                  ) @ s["w2"]["kernel"]
        gate = 1 / (1 + np.exp(-(m[t] @ p["shared_gate"]["kernel"])))
        want[t] += gate[0] * shared
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 32), want,
                               rtol=2e-5, atol=2e-5)
    share = np.bincount(top.ravel(), minlength=16) / m.shape[0]
    by_hand = 0.01 * 16 * np.sum(share * prob.mean(0))
    sown, = jax.tree_util.tree_leaves(state["losses"])
    np.testing.assert_allclose(float(sown), by_hand, rtol=1e-5)
    # no bias step: the declared bias stays at zero
    np.testing.assert_array_equal(state["router_state"]["bias"], 0)


def test_balance_loss_needs_the_softmax_score():
    layer = DroplessExperts(width=12, n_routed=16, n_held=4, top_k=3,
                            aux_loss_coef=0.01)
    with pytest.raises(ValueError, match="softmax"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 32)))


def test_the_shares_add_up_to_the_uncut_layer():
    """The 4 shares of a 16-expert layer (4 experts each, 3 a token),
    the gated shared expert counted once, sum to the reference's uncut
    layer; a share with the shared expert's gate left out is another
    layer."""
    config = dict(CONFIG, num_experts=16, first_expert_held=0)
    whole = _experts(aux=0.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    variables = whole.init(jax.random.PRNGKey(1), x)
    p = variables["params"]
    weights = {
        "router": p["router"]["kernel"],
        "experts": (p["w1"], p["w3"], p["w2"]),
        "shared": tuple(p["shared"][k]["kernel"] for k in ("w1", "w3", "w2")),
        "shared_gate": p["shared_gate"]["kernel"]}
    m = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        uncut, chosen, _ = ref.expert_layer(m, weights, config)
        assert chosen.shape == (32, 3)
        shared_only = (ref.swiglu(m, weights["shared"])
                       * jax.nn.sigmoid(m @ weights["shared_gate"]))
        total = jnp.zeros_like(m)
        for share in range(4):
            part = _experts(n_held=4, first_held=4 * share, aux=0.0)
            held = {**p, **{k: p[k][4 * share:4 * share + 4]
                            for k in ("w1", "w3", "w2")}}
            out = part.apply({**variables, "params": held}, x)
            total = total + out.reshape(-1, 32) - shared_only
        np.testing.assert_allclose(total + shared_only, uncut, atol=5e-5,
                                   rtol=5e-5)
        with ref.faulty("no_shared_gate"):
            wrong, _, _ = ref.expert_layer(m, weights, config)
    assert _rel(wrong, uncut) > 0.05


# ------------------------------------------------------------------ #
# through the Estimator                                              #
# ------------------------------------------------------------------ #
def test_fit_predict_and_counters(compiled_anew):
    """compile / fit / predict like the other zoo models: the loss
    (with the balance term) falls, the router's bias stays at zero, the
    delta rule's chunks and the expert counters publish under the
    model's module names."""
    _, model = _model()
    rng = np.random.default_rng(3)
    ids = rng.integers(1, 64, (16, 33)).astype(np.int32)
    x, y = {"input_ids": ids[:, :-1]}, ids[:, 1:]
    model.compile(optimizer=AdamWeightDecay(lr=3e-3), seed=0)

    def published(name, module):
        family = get_registry().snapshot().get(name)
        return sum(v for k, v in (family or {"values": {}})[
            "values"].items() if f"module={module}," in k)

    chunks = "zoo_model_delta_rule_chunks_total"
    before = (published(chunks, "layer_1/linear_mixer"),
              published("zoo_model_moe_assignments_total", "layer_3/moe"))
    history = model.fit((x, y), batch_size=8, epochs=3)
    assert history[-1]["loss"] < history[0]["loss"]
    bias = model.estimator.variables["router_state"]["layer_1"]["moe"]["bias"]
    assert float(jnp.abs(bias).max()) == 0
    logits = model.predict(x, batch_size=8)
    assert logits.shape == (16, 32, 64) and logits.dtype == np.float32
    # 3 epochs x 2 steps x 8 rows x 4 value heads x 32 / 8 chunks
    assert published(chunks, "layer_1/linear_mixer") - before[0] == (
        3 * 2 * 8 * 4 * 4)
    assert published(chunks, "layer_3/attention") == 0
    # 3 epochs x 2 steps x 8 rows x 32 tokens x 3 experts a token
    assert published("zoo_model_moe_assignments_total",
                     "layer_3/moe") - before[1] == 3 * 2 * 8 * 32 * 3
