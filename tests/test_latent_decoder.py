"""The latent-attention decoder against its plain reference
(``benchmark/reference/moonlight.py``) at tiny widths on the CPU: the
flash kernels at two head widths and with a shared key head, the
latent attention module, the expert layer's share with two shared
experts, the grouped products' tiling, and the whole model through
``Estimator``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.layers import moe
from analytics_zoo_tpu.keras.layers.latent_decoder import LatentAttention
from analytics_zoo_tpu.keras.layers.moe import (
    DroplessExperts, grouped_dot_tiling)
from analytics_zoo_tpu.learn.optim import AdamWeightDecay
from analytics_zoo_tpu.models.text import LatentDecoderLM
from analytics_zoo_tpu.models.text.sparse_decoder_lm import (
    LatentDecoderModule, next_token_loss)
from analytics_zoo_tpu.obs.metrics import get_registry
from analytics_zoo_tpu.ops import attention, pallas_attention
from analytics_zoo_tpu.ops.attention import (
    attention_path, dot_product_attention, reference_attention)
from analytics_zoo_tpu.ops.pallas_attention import pallas_flash_attention_fwd
from benchmark.reference import moonlight as ref

CONFIG = dict(
    hidden_size=32, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=12, kv_lora_rank=20,
    intermediate_size=48, moe_intermediate_size=24,
    n_routed_experts_routed_over=16, n_routed_experts=4,
    first_expert_held=4, num_experts_per_tok=3, n_shared_experts=2,
    routed_scaling_factor=2.446, norm_topk_prob=True, rms_norm_eps=1e-5,
    rope_theta=50000, vocab_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, router_bias_update_speed=0.001)


def _model(dtype="float32", **changes):
    c = {**CONFIG, **changes}
    return c, LatentDecoderLM(
        vocab=c["vocab_size"], hidden_size=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        n_dense_layers=c["first_k_dense_replace"],
        n_head=c["num_attention_heads"], nope_dim=c["qk_nope_head_dim"],
        rope_dim=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
        latent_dim=c["kv_lora_rank"], dense_width=c["intermediate_size"],
        expert_width=c["moe_intermediate_size"],
        n_routed=c["n_routed_experts_routed_over"],
        n_held=c["n_routed_experts"], first_held=c["first_expert_held"],
        top_k=c["num_experts_per_tok"],
        route_scale=c["routed_scaling_factor"],
        n_shared=c["n_shared_experts"],
        bias_step=c["router_bias_update_speed"],
        rope_theta=c["rope_theta"], eps=c["rms_norm_eps"], dtype=dtype)


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
# the kernels: two widths, a shared key head                         #
# ------------------------------------------------------------------ #
def _latent_operands(h, lq, lk, d_nope, d_rot, d_v, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (1, h, lq, d_nope + d_rot)),
            jax.random.normal(ks[1], (1, h, lk, d_nope)),
            jax.random.normal(ks[2], (1, 1, lk, d_rot)),
            jax.random.normal(ks[3], (1, h, lk, d_v)),
            jax.random.normal(ks[4], (1, h, lq, d_v)))


def _explicit(q, k_nope, k_rot, v):
    """Scores of the joined keys under the causal mask written out."""
    lq, lk = q.shape[2], k_nope.shape[2]
    keep = (np.arange(lk)[None] <= np.arange(lq)[:, None] + (lk - lq))
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rot, k_nope.shape[:-1] + k_rot.shape[-1:])], axis=-1)
    return reference_attention(q, k, v, mask=jnp.asarray(keep)[None, None])


@pytest.mark.parametrize("path", ["fused", "split"])
@pytest.mark.parametrize("h,lq,lk,widths,blocks", [
    (2, 256, 256, (128, 64, 128), (128, 128)),   # the published widths
    (3, 128, 384, (64, 64, 192), (128, 128)),    # cross-length, wider values
    (2, 256, 256, (128, 64, 64), (256, 128)),    # one row block
    (3, 512, 512, (128, 64, 128), (128, 128)),   # dQ over four kv-blocks
])
def test_flash_two_widths_and_a_shared_key_match_explicit_mask(
        monkeypatch, h, lq, lk, widths, blocks, path):
    """The owned kernels in interpret mode, values ``widths[2]`` wide
    under queries ``widths[0] + widths[1]`` wide whose last columns read
    one key head shared by all: the output and all four gradients, from
    the one backward kernel and (its budget set to nothing) from the
    two that hold only blocks."""
    if path == "split":
        monkeypatch.setattr(pallas_attention, "FUSED_BWD_VMEM_BUDGET", 0)
    assert pallas_attention.flash_backward_path(
        lq, lk, widths[0] + widths[1], widths[0], widths[2], 4, None,
        *blocks) == path
    q, k_nope, k_rot, v, ct = _latent_operands(h, lq, lk, *widths)

    def flash(q, k_nope, k_rot, v):
        return pallas_flash_attention_fwd(q, k_nope, v, True, None, *blocks,
                                          None, k_rot)

    np.testing.assert_allclose(flash(q, k_nope, k_rot, v),
                               _explicit(q, k_nope, k_rot, v),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * ct),
                   argnums=(0, 1, 2, 3))(q, k_nope, k_rot, v)
    want = jax.grad(lambda *a: jnp.sum(_explicit(*a) * ct),
                    argnums=(0, 1, 2, 3))(q, k_nope, k_rot, v)
    for g, w, name in zip(got, want, ("dq", "dk", "dk_shared", "dv")):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4, err_msg=name)


def test_shared_key_gradients_in_bfloat16_over_several_blocks():
    """192 | 128 in the compute dtype of the cell, blocks of 128 at
    L512: the shared head's gradient is summed over the heads in
    float32 and every gradient comes back in bfloat16, close to the
    float32 reference's on the same rounded operands."""
    operands = [a.astype(jnp.bfloat16)
                for a in _latent_operands(4, 512, 512, 128, 64, 128)]
    ct = operands.pop().astype(jnp.float32)

    def flash(q, k_nope, k_rot, v):
        return pallas_flash_attention_fwd(q, k_nope, v, True, None, 128, 128,
                                          None, k_rot)

    got = jax.grad(lambda *a: jnp.sum(flash(*a).astype(jnp.float32) * ct),
                   argnums=(0, 1, 2, 3))(*operands)
    want = jax.grad(lambda *a: jnp.sum(_explicit(*a) * ct),
                    argnums=(0, 1, 2, 3))(
        *(a.astype(jnp.float32) for a in operands))
    for g, w, name in zip(got, want, ("dq", "dk", "dk_shared", "dv")):
        assert g.shape == w.shape and g.dtype == jnp.bfloat16, name
        assert _rel(g, w) < 1.5e-2, name


def test_flash_two_widths_without_a_shared_key():
    """Keys as wide as the queries, values narrower, grouped heads and a
    window: the kernels' other variants at two widths."""
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (1, 4, 256, 192))
    k = jax.random.normal(ks[1], (1, 2, 256, 192))
    v = jax.random.normal(ks[2], (1, 2, 256, 128))
    ct = jax.random.normal(ks[3], (1, 4, 256, 128))
    rows, keys = np.arange(256)[:, None], np.arange(256)[None]
    mask = jnp.asarray((keys <= rows) & (rows - keys < 100))[None, None]

    def flash(q, k, v):
        return pallas_flash_attention_fwd(q, k, v, True, None, 128, 128, 100)

    def explicit(q, k, v):
        return reference_attention(q, jnp.repeat(k, 2, 1),
                                   jnp.repeat(v, 2, 1), mask=mask)

    np.testing.assert_allclose(flash(q, k, v), explicit(q, k, v),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * ct), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(explicit(*a) * ct),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)


def test_kernel_refuses_widths_that_do_not_add_up():
    q, k_nope, k_rot, v, _ = _latent_operands(2, 128, 128, 128, 64, 128)
    with pytest.raises(ValueError, match="keys are 128 wide, queries 192"):
        pallas_flash_attention_fwd(q, k_nope, v, True)
    with pytest.raises(ValueError, match="a shared key is one head"):
        pallas_flash_attention_fwd(q, k_nope, v, True, None, None, None,
                                   None, jnp.repeat(k_rot, 2, 1))
    with pytest.raises(ValueError, match="multiples of 64"):
        pallas_flash_attention_fwd(q, k_nope, v[..., :96], True, None,
                                   None, None, None, k_rot)


def test_dispatcher_takes_the_values_width_and_names_the_call(monkeypatch):
    """``attention_path`` is a function of the shapes: values 128 wide
    under 192-wide keys ride the owned kernel, which the stock kernel
    cannot; the call names itself ``attention_<path>_latent``."""
    assert attention_path("tpu", 8192, 8192, 192, 16, 16, value_dim=128,
                          causal=True) == "flash"
    assert attention_path("tpu", 8192, 8192, 192, 16, 16, value_dim=96,
                          causal=True) == "einsum"
    assert attention_path("tpu", 1024, 1024, 64, 4, 4, value_dim=64,
                          key_padding_mask=True) == "stock_pallas"
    assert attention_path("tpu", 1024, 1024, 64, 4, 4, value_dim=128,
                          key_padding_mask=True) == "einsum"
    assert attention_path("cpu", 8192, 8192, 192, 16, 16,
                          value_dim=128) == "einsum"
    q, k_nope, k_rot, v, _ = _latent_operands(2, 1024, 1024, 128, 64, 128)

    def call(q, k_nope, k_rot, v):
        return dot_product_attention(q, k_nope, v, causal=True,
                                     k_shared=k_rot)

    np.testing.assert_allclose(call(q, k_nope, k_rot, v),
                               _explicit(q, k_nope, k_rot, v),
                               atol=2e-5, rtol=2e-5)
    assert "attention_einsum_latent" in jax.jit(call).lower(
        q, k_nope, k_rot, v).as_text(debug_info=True)
    monkeypatch.setattr(attention, "_platform", lambda q: "tpu")
    # (a new function: the trace of ``call`` above is cached)
    text = str(jax.make_jaxpr(lambda *a: call(*a))(q, k_nope, k_rot, v))
    assert text.count("pallas_call[") == 1
    np.testing.assert_allclose(call(q, k_nope, k_rot, v),
                               _explicit(q, k_nope, k_rot, v),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------------ #
# the attention module                                               #
# ------------------------------------------------------------------ #
def test_latent_attention_matches_reference_and_ropes_64_of_192():
    """The module against the reference's branch; and positions reach
    the scores through the rotary columns only: with RoPE's angle
    zeroed (theta -> infinity keeps position 0's) the output changes."""
    module = LatentAttention(n_head=4, nope_dim=16, rope_dim=8, v_dim=12,
                             latent_dim=20, rope_theta=50000.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 32))
    variables = module.init(jax.random.PRNGKey(1), x)
    a = variables["params"]
    assert a["q"]["kernel"].shape == (32, 4 * 24)
    assert a["kv_down"]["kernel"].shape == (32, 20 + 8)
    assert a["kv_up"]["kernel"].shape == (20, 4 * (16 + 12))
    assert a["out"]["kernel"].shape == (4 * 12, 32)
    layer = {"wq": a["q"]["kernel"], "wkva": a["kv_down"]["kernel"],
             "latent_norm": a["latent_norm"]["scale"],
             "wkvb": a["kv_up"]["kernel"], "wo": a["out"]["kernel"]}
    with jax.default_matmul_precision("highest"):
        got = module.apply(variables, x)[0]
        want = ref.latent_attention(x[0], layer, CONFIG)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        with ref.faulty("no_rope"):
            assert _rel(ref.latent_attention(x[0], layer, CONFIG), want) > 0.01


# ------------------------------------------------------------------ #
# the share                                                          #
# ------------------------------------------------------------------ #
def test_the_shares_add_up_to_the_uncut_layer():
    """The 8 shares of a 64-expert layer (8 experts each, 6 a token),
    the two shared experts counted once, sum to the reference's uncut
    layer."""
    config = dict(CONFIG, n_routed_experts_routed_over=64,
                  n_routed_experts=64, first_expert_held=0,
                  num_experts_per_tok=6)
    whole = DroplessExperts(width=24, n_routed=64, n_held=64, top_k=6,
                            route_scale=2.446, shared_width=48)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    variables = whole.init(jax.random.PRNGKey(1), x)
    bias = jax.random.normal(jax.random.PRNGKey(7), (64,)) * 0.3
    variables = {**variables, "router_state": {"bias": bias}}
    p = variables["params"]
    weights = {
        "router": p["router"]["kernel"], "bias": bias,
        "experts": (p["w1"], p["w3"], p["w2"]),
        "shared": tuple(p["shared"][k]["kernel"]
                        for k in ("w1", "w3", "w2"))}
    m = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        uncut, chosen = ref.expert_layer(m, weights, config)
        assert chosen.shape == (32, 6)
        shared_only = ref.swiglu(m, weights["shared"])
        total = jnp.zeros_like(m)
        for share in range(8):
            part = DroplessExperts(
                width=24, n_routed=64, n_held=8, first_held=8 * share,
                top_k=6, route_scale=2.446, shared_width=48)
            held = {**p, **{k: p[k][8 * share:8 * share + 8]
                            for k in ("w1", "w3", "w2")}}
            out = part.apply({**variables, "params": held}, x)
            total = total + out.reshape(-1, 32) - shared_only
        np.testing.assert_allclose(total + shared_only, uncut,
                                   atol=5e-5, rtol=5e-5)
        # one shared expert for two is another layer
        with ref.faulty("one_shared_expert"):
            wrong, _ = ref.expert_layer(m, weights, config)
        assert _rel(wrong, uncut) > 0.05


# ------------------------------------------------------------------ #
# the grouped products' tiling                                       #
# ------------------------------------------------------------------ #
def test_grouped_dot_tiling_is_a_function_of_the_shapes():
    """Today's tile at 1,024-wide experts under d 2,048, every pass;
    tiles that divide at 1,408 = 11 x 128."""
    for k, n in ((2048, 2048), (1024, 2048), (2048, 1024)):
        assert grouped_dot_tiling(k, n) == (512, 1024, 1024)
    # forward, its transpose and the weights' gradient, both products
    for k, n in ((2048, 2816), (2816, 2048), (1408, 2048), (2048, 1408)):
        rows, tile_k, tile_n = grouped_dot_tiling(k, n)
        assert rows == 512
        assert k % tile_k == 0 and n % tile_n == 0
        assert tile_k % 128 == 0 and tile_n % 128 == 0
        # the 11 x 128 side whole, and what VMEM leaves of the other
        assert {tile_k, tile_n} == {1408, 512}
    assert grouped_dot_tiling(1408, 2048) == (512, 1408, 512)
    assert grouped_dot_tiling(96, 200) == (512, 128, 128)


def test_grouped_product_and_its_gradients_in_interpret_mode():
    """``megablox``' kernels as ``grouped_dot`` calls them off the CPU
    (here interpreted), each pass with its own tile, against
    ``ragged_dot``: the product and both gradients, a width that no
    tile but 128 divides among them."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (1024, 256))
    w = jax.random.normal(ks[1], (3, 256, 384))
    ct = jax.random.normal(ks[2], (1024, 384))
    sizes = jnp.asarray([500, 0, 300], jnp.int32)
    assert grouped_dot_tiling(256, 384) == (512, 256, 384)
    assert grouped_dot_tiling(2048, 384) == (512, 1024, 384)

    def through(product):
        return jax.value_and_grad(
            lambda x, w: jnp.sum((product(x, w, sizes) * ct)[:800]),
            argnums=(0, 1))(x, w)

    (got, (dx, dw)), (want, (dx_, dw_)) = through(moe._gmm), through(
        jax.lax.ragged_dot)
    assert abs(float(got) - float(want)) < 1e-2
    np.testing.assert_allclose(dx[:800], dx_[:800], atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(dw, dw_, atol=1e-3, rtol=1e-3)


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
    assert len(got) == len(wanted) > 30
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


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_moves_the_reference(fault):
    """What the cell's tolerance is set against: every listed mistake
    changes the logits by far more than float32 rounding."""
    config, model = _model()
    variables, x, _ = _seeded(model)
    want = ref.forward(variables, {"input_ids": x}, config)
    with ref.faulty(fault):
        wrong = ref.forward(variables, {"input_ids": x}, config)
    assert _rel(wrong, want) > 1e-3
    with pytest.raises(ValueError, match="unknown fault"):
        with ref.faulty("no_such_fault"):
            pass


def test_model_has_two_norms_a_layer_and_no_gate():
    _, model = _model()
    variables, _, _ = _seeded(model)
    p = variables["params"]
    assert set(p["layer_0"]) == {"attention", "input_norm", "pre_mlp_norm",
                                 "mlp"}
    assert set(p["layer_1"]) == {"attention", "input_norm", "pre_mlp_norm",
                                 "moe"}
    assert set(p["layer_1"]["attention"]) == {"q", "kv_down", "latent_norm",
                                              "kv_up", "out"}
    assert p["layer_1"]["moe"]["shared"]["w1"]["kernel"].shape == (32, 48)
    assert set(variables["router_state"]) == {"layer_1", "layer_2"}


# ------------------------------------------------------------------ #
# rematerialisation keeps the flash kernel's two results             #
# ------------------------------------------------------------------ #
def test_remat_layer_backward_holds_two_flash_kernels(monkeypatch):
    """Forward + logsumexp and one backward kernel a layer at L1024,
    which the flash path takes: the layer's second forward holds no
    attention kernel, because both results of the first are kept."""
    monkeypatch.setattr(attention, "_platform", lambda q: "tpu")
    module = LatentDecoderModule(
        vocab=64, hidden_size=64, n_layers=2, n_dense_layers=2, n_head=2,
        nope_dim=64, rope_dim=64, v_dim=64, latent_dim=32, dense_width=96,
        expert_width=16, n_routed=8, n_held=4)
    ids = np.random.default_rng(0).integers(0, 64, (1, 1024)).astype(
        np.int32)
    params = module.init(jax.random.PRNGKey(0), ids)["params"]

    def loss(params):
        return next_token_loss(module.apply({"params": params}, ids),
                               jnp.roll(ids, -1, 1))

    text = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert text.count("pallas_call[") == 2 * 2
    assert "name=flash_attention_out" in text
    # the shared rotary key is kept as one head, beside q, k_nope and v
    assert text.count("name=attention_k_rot]") == 2
    assert "bf16" not in text       # float32 stays float32 when kept


# ------------------------------------------------------------------ #
# through the Estimator                                              #
# ------------------------------------------------------------------ #
def test_fit_predict_and_counters(compiled_anew):
    """compile / fit / predict like the other zoo models: the loss
    falls, the router's bias moves without a gradient, the expert
    counters publish under the model's module names."""
    _, model = _model()
    rng = np.random.default_rng(3)
    ids = rng.integers(1, 64, (16, 17)).astype(np.int32)
    x, y = {"input_ids": ids[:, :-1]}, ids[:, 1:]
    model.compile(optimizer=AdamWeightDecay(lr=3e-3), seed=0)

    def published():
        family = get_registry().snapshot().get(
            "zoo_model_moe_assignments_total")
        return {k: v for k, v in (family or {"values": {}})[
            "values"].items() if "layer_1/moe" in k}

    before = published()
    history = model.fit((x, y), batch_size=8, epochs=3)
    assert history[-1]["loss"] < history[0]["loss"]
    bias = model.estimator.variables["router_state"]["layer_1"]["moe"]["bias"]
    assert float(jnp.abs(bias).max()) > 0
    logits = model.predict(x, batch_size=8)
    assert logits.shape == (16, 16, 64) and logits.dtype == np.float32
    grown = {k: v - before.get(k, 0) for k, v in published().items()}
    # 3 epochs x 2 steps x 8 rows x 16 tokens x 3 experts a token
    assert len(grown) == 1 and sum(grown.values()) == 3 * 2 * 8 * 16 * 3
