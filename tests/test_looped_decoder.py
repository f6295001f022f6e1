"""The looped decoder against its plain reference
(``benchmark/reference/ouro.py``) at tiny widths on the CPU: the layer,
every pass's logits, the exit distribution, the exit-weighted loss and
every gradient, weight sharing against untied copies, the one-pass
case against a plain decoder, the blocked heads against whole logits,
causality, the rematerialisation policy, and the whole model through
``Estimator``."""

import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.layers.looped_decoder import LoopedDecoderLayer
from analytics_zoo_tpu.keras.layers.sparse_decoder import RMSNorm
from analytics_zoo_tpu.learn.optim import AdamWeightDecay
from analytics_zoo_tpu.models.text import LoopedDecoderLM
from analytics_zoo_tpu.models.text import looped_decoder_lm as lm
from analytics_zoo_tpu.models.text.sparse_decoder_lm import next_token_loss
from analytics_zoo_tpu.obs.metrics import get_registry
from analytics_zoo_tpu.ops import attention
from benchmark.lib import flops_ouro
from benchmark.reference import ouro as ref

CONFIG = dict(
    hidden_size=64, num_attention_heads=2, head_dim=32, intermediate_size=96,
    vocab_size=50, num_hidden_layers=2, total_ut_steps=3, rms_norm_eps=1e-6,
    rope_theta=1000000, exit_entropy_beta=0.1)
LENGTH = 48
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model(dtype="float32", **changes):
    c = {**CONFIG, **changes}
    return c, LoopedDecoderLM(
        vocab=c["vocab_size"], hidden_size=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_passes=c["total_ut_steps"],
        n_head=c["num_attention_heads"], head_dim=c["head_dim"],
        dense_width=c["intermediate_size"], beta=c["exit_entropy_beta"],
        rope_theta=c["rope_theta"], eps=c["rms_norm_eps"], init_std=0.05,
        dtype=dtype)


def _seeded(model, seed=0, length=LENGTH, rows=2):
    """Variables from the seed (norm scales moved off their start and
    the gate off zero, so that each shows), ids and next-token labels."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, model._config["vocab"], (rows, length + 1))
    x, y = ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)
    variables = model.estimator.adapter.init(jax.random.PRNGKey(seed),
                                             {"input_ids": x})

    def moved(path, a):
        if path[-1].key == "scale":
            return a * jnp.asarray(rng.uniform(0.5, 1.5, a.shape), jnp.float32)
        if path[-1].key.startswith("exit_gate"):
            return jnp.asarray(rng.normal(0, 0.3, a.shape), jnp.float32)
        return a

    variables["params"] = jax.tree_util.tree_map_with_path(
        moved, variables["params"])
    return variables, x, y


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def _train_outputs(model, variables, x):
    out, _ = model.estimator.adapter.apply(variables, {"input_ids": x},
                                           training=True)
    return out


def _pass_logits(out):
    """[B, T, L, V] float32 from the training outputs, whole."""
    return jnp.einsum("tbld,dv->btlv", out["states"],
                      out["head"].astype(out["states"].dtype),
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ #
# layer and model against the reference                              #
# ------------------------------------------------------------------ #
def test_layer_matches_reference():
    c = CONFIG
    layer = LoopedDecoderLayer(
        n_head=2, head_dim=32, dense_width=96, rope_theta=c["rope_theta"],
        eps=c["rms_norm_eps"], init_std=0.05)
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.normal(0, 1, (1, LENGTH, 64)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), u)["params"]
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * jnp.asarray(rng.uniform(0.5, 1.5, a.shape),
                                        jnp.float32)
        if path[-1].key == "scale" else a, params)
    assert sorted(params) == ["attention", "input_norm", "mlp",
                              "post_attention_norm", "post_mlp_norm",
                              "pre_mlp_norm"]
    got = layer.apply({"params": params}, u)
    neutral = ref.weights_from_program({"params": {
        "stack": {"layer_0": params, "final_norm": {"scale": jnp.ones(64)}},
        "embed": {"embedding": jnp.zeros((1, 64))}, "head": jnp.zeros((64, 1)),
        "exit_gate_kernel": jnp.zeros(64), "exit_gate_bias": jnp.zeros(1)}})
    with jax.default_matmul_precision("highest"):
        want = ref.layer_forward(u[0], neutral["layers"][0], c)
    assert _rel(got[0], want) < 2e-5


def test_model_matches_reference_in_float32():
    """Every pass's logits, the exit distribution, the loss and every
    gradient (the gate's and every shared weight's) against the plain
    reference; ``predict``'s output is the last pass's logits."""
    c, model = _model()
    variables, x, y = _seeded(model)
    out = _train_outputs(model, variables, x)
    want_z, want_p = ref.forward_all(variables, x, c)
    assert out["states"].shape == (3, 2, LENGTH, 64)
    assert _rel(_pass_logits(out), want_z) < 2e-5
    assert _rel(jnp.moveaxis(jnp.exp(out["log_exit"]), 0, 1), want_p) < 2e-5
    lse = jax.nn.logsumexp(want_z, -1)                        # [B, T, L]
    assert _rel(jnp.moveaxis(out["lse"], 0, 1), lse) < 2e-5

    z_t, _ = model.estimator.adapter.apply(variables, {"input_ids": x},
                                           training=False)
    assert z_t.shape == (2, LENGTH, 50) and z_t.dtype == jnp.float32
    assert _rel(z_t, ref.forward(variables, x, c)) < 2e-5
    assert _rel(z_t, want_z[:, -1]) < 2e-5

    def loss(params):
        return model.estimator.loss_fn(
            _train_outputs(model, {**variables, "params": params}, x), y)

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
    variables, x, y = _seeded(model)
    z_t, _ = model.estimator.adapter.apply(variables, {"input_ids": x},
                                           training=False)
    assert z_t.dtype == jnp.float32
    assert _rel(z_t, ref.forward(variables, x, c)) < 0.05
    got = model.estimator.loss_fn(_train_outputs(model, variables, x), y)
    assert abs(float(got) - float(ref.loss(variables, x, y, c))) < 0.02


def test_parameter_tree_holds_each_layer_once_at_the_published_widths():
    """612,438,017 parameters at 8 layers whatever the passes: the tree
    has ``n`` layers, not ``T x n``."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        config = json.load(f)
    module = lm.LoopedDecoderModule(
        vocab=config["vocab_size"], hidden_size=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_passes=config["total_ut_steps"],
        n_head=config["num_attention_heads"], head_dim=config["head_dim"],
        dense_width=config["intermediate_size"])
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes["params"]))
    assert count == flops_ouro.params(config) == 612_438_017
    assert sorted(shapes["params"]["stack"]) == sorted(
        [f"layer_{i}" for i in range(8)] + ["final_norm"])
    assert shapes["counters"]["loop_pass_loss_millinats"].shape == (4,)


def test_shared_weights_gradient_is_the_sum_over_untied_copies():
    """The reference with ``T`` separate copies of the layers, each
    equal to the shared one: the program's gradient of a shared weight
    is the sum of the copies' gradients."""
    c, model = _model()
    variables, x, y = _seeded(model)
    y = jnp.asarray(y)

    def program(params):
        return model.estimator.loss_fn(
            _train_outputs(model, {**variables, "params": params}, x), y)

    got = jax.grad(program)(variables["params"])["stack"]
    w = ref.weights_from_program(variables)

    def untied(passes):
        return ref.weights_loss({**w, "passes": passes}, x, y, c,
                                c["exit_entropy_beta"])

    copies = jax.grad(untied)([w["layers"]] * c["total_ut_steps"])
    for i in range(c["num_hidden_layers"]):
        for j, name in enumerate(("q", "k", "v", "out")):
            summed = sum(copy[i]["attention"][j] for copy in copies)
            alone = copies[0][i]["attention"][j]
            mine = got[f"layer_{i}"]["attention"][name]["kernel"]
            assert _rel(mine, summed) < 5e-5, (i, name)
            assert _rel(mine, alone) > 1e-3, (i, name)
        for j, name in enumerate(("w1", "w3", "w2")):
            summed = sum(copy[i]["mlp"][j] for copy in copies)
            assert _rel(got[f"layer_{i}"]["mlp"][name]["kernel"],
                        summed) < 5e-5, (i, name)


class _PlainStack(nn.Module):
    """The same layers applied once, by hand: embedding, layers, final
    norm, head."""

    @nn.compact
    def __call__(self, ids):
        u = nn.Embed(50, 64, name="embed")(ids)
        for i in range(2):
            u = LoopedDecoderLayer(
                n_head=2, head_dim=32, dense_width=96,
                rope_theta=CONFIG["rope_theta"], eps=CONFIG["rms_norm_eps"],
                name=f"layer_{i}")(u)
        h = RMSNorm(CONFIG["rms_norm_eps"], name="final_norm")(u)
        head = self.param("head", nn.initializers.normal(0.02), (64, 50))
        return jnp.dot(h, head, preferred_element_type=jnp.float32)


def test_one_pass_is_the_next_token_loss_of_the_plain_stack():
    c, model = _model(total_ut_steps=1)
    variables, x, y = _seeded(model)
    p = variables["params"]
    plain = {"embed": p["embed"], "head": p["head"],
             "final_norm": p["stack"]["final_norm"],
             **{k: v for k, v in p["stack"].items() if k.startswith("layer")}}
    want = next_token_loss(_PlainStack().apply({"params": plain},
                                               jnp.asarray(x)), y)
    out = _train_outputs(model, variables, x)
    assert np.array_equal(out["log_exit"], np.zeros((1, 2, LENGTH)))
    # the gate's term has nothing to say: any beta gives the same loss
    for beta in (0.0, 0.1, 5.0):
        got = lm.exit_weighted_loss(out, y, beta=beta)
        assert abs(float(got) - float(want)) < 2e-5, beta


# ------------------------------------------------------------------ #
# the exit distribution and the loss                                 #
# ------------------------------------------------------------------ #
def test_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest():
    rng = np.random.default_rng(2)
    gate = jnp.asarray(rng.normal(0, 2, (4, 3, 7)), jnp.float32)
    p = lm.exit_distribution(gate)
    assert p.shape == (4, 3, 7)
    assert np.allclose(jnp.sum(p, 0), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(gate)
    assert np.allclose(p[0], lam[0], atol=1e-6)
    assert np.allclose(p[1], lam[1] * (1 - lam[0]), atol=1e-6)
    assert np.allclose(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]),
                       atol=1e-6)
    # the last gate decides nothing
    other = lm.exit_distribution(gate.at[3].set(-gate[3]))
    assert np.array_equal(p, other)


def test_entropy_term_rewards_a_flat_exit_distribution():
    """``- beta H(p)``: the loss falls as ``beta`` grows, by exactly
    the entropy, and a gradient step on the gate's bias at a large
    ``beta`` flattens ``p``."""
    _, model = _model()
    variables, x, y = _seeded(model)
    out = _train_outputs(model, variables, x)
    entropy = float(jnp.mean(jnp.sum(jax.scipy.special.entr(
        jnp.exp(out["log_exit"])), 0)))
    assert entropy > 0
    at = [float(lm.exit_weighted_loss(out, y, beta=b)) for b in (0.0, 1.0)]
    assert abs((at[0] - at[1]) - entropy) < 1e-5

    def entropy_after_a_step(beta):
        def loss(gate):
            return lm.exit_weighted_loss(
                {**out, "log_exit": lm.exit_log_distribution(gate)}, y,
                beta=beta)

        gate = jnp.asarray(np.random.default_rng(5).normal(
            1.5, 0.5, (3, 2, LENGTH)), jnp.float32)
        stepped = gate - 5.0 * x.size * jax.grad(loss)(gate)
        return float(jnp.mean(jnp.sum(jax.scipy.special.entr(
            lm.exit_distribution(stepped)), 0)))

    assert entropy_after_a_step(10.0) > entropy_after_a_step(0.0)


@pytest.mark.parametrize("bias", [-30.0, 30.0, -1e4, 1e4])
def test_a_gate_that_has_run_to_one_end_leaves_the_gradients_finite(bias):
    """``sigmoid(30)`` is exactly 1 in float32, so every later pass's
    probability is exactly 0: the loss and every gradient stay finite,
    and the distribution still sums to 1."""
    _, model = _model()
    variables, x, y = _seeded(model)
    params = dict(variables["params"])
    params["exit_gate_bias"] = jnp.full((1,), bias, jnp.float32)

    def loss(params):
        return model.estimator.loss_fn(
            _train_outputs(model, {**variables, "params": params}, x), y)

    value, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(value))
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert np.all(np.isfinite(np.asarray(g))), path
    p = lm.exit_distribution(jnp.full((4, 1, 5), bias, jnp.float32))
    assert np.allclose(jnp.sum(p, 0), 1.0, atol=1e-6)
    assert float(p[0 if bias > 0 else 3].min()) > 0.999


def test_a_long_epoch_wraps_no_counter():
    """``Estimator._publish_counters`` takes a counter's growth modulo
    2**32: the bound that ``_count`` states, at the published
    vocabulary's starting loss and at an exit probability of 1."""
    assert round(lm.COUNT_SCALE * np.log(49_152)) * 390_000 < 2 ** 32
    assert lm.COUNT_SCALE * 4_200_000 < 2 ** 32


def test_blocked_heads_equal_whole_logits_with_gradients():
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(0, 1, (96, 32)), jnp.float32)
    head = jnp.asarray(rng.normal(0, 0.3, (32, 70)), jnp.float32)
    weight = jnp.asarray(rng.normal(0, 1, (96,)), jnp.float32)

    def blocked(h, head):
        return jnp.sum(weight * lm.blocked_logsumexp(h, head, 16,
                                                     jnp.float32))

    def whole(h, head):
        return jnp.sum(weight * jax.nn.logsumexp(h @ head, -1))

    got = jax.value_and_grad(blocked, (0, 1))(h, head)
    want = jax.value_and_grad(whole, (0, 1))(h, head)
    assert abs(float(got[0]) - float(want[0])) < 1e-4
    assert _rel(got[1][0], want[1][0]) < 2e-5
    assert _rel(got[1][1], want[1][1]) < 2e-5
    with pytest.raises(ValueError):
        lm.blocked_logsumexp(h, head, 36, jnp.float32)


def test_loss_equals_the_unblocked_one_with_gradients():
    """``exit_weighted_loss`` on the module's outputs against the same
    loss written over whole [B, T, L, V] logits."""
    _, model = _model()
    variables, x, y = _seeded(model)
    y = jnp.asarray(y)

    def got(params):
        return model.estimator.loss_fn(
            _train_outputs(model, {**variables, "params": params}, x), y)

    def want(params):
        out = _train_outputs(model, {**variables, "params": params}, x)
        z = _pass_logits(out)
        nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, y[:, None, :, None], -1)[..., 0]              # [B, T, L]
        p = jnp.moveaxis(jnp.exp(out["log_exit"]), 0, 1)
        return jnp.mean(jnp.sum(p * nll + 0.1 * p * jnp.log(p), 1))

    a, b = (jax.value_and_grad(f)(variables["params"]) for f in (got, want))
    assert abs(float(a[0]) - float(b[0])) < 2e-5
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(a[1]),
                            jax.tree_util.tree_leaves(b[1])):
        assert _rel(g, w) < 5e-5, jax.tree_util.keystr(path)


def test_train_step_holds_no_whole_pass_of_logits(monkeypatch):
    """The jaxpr of a whole train step at L = 8 head blocks of 16 rows:
    the most rows any value holds beside the vocabulary is the head's
    own 64 (``[d, V]`` and its gradient), so no [L, V] array of any
    pass exists, let alone of more than one."""
    monkeypatch.setattr(lm, "HEAD_BLOCK_ROWS", 16)
    _, model = _model()
    variables, x, y = _seeded(model, length=128, rows=1)
    model.compile(optimizer=AdamWeightDecay(lr=1e-3), seed=0)
    est = model.estimator
    opt_state = est.tx.init(variables["params"])
    jaxpr = jax.make_jaxpr(est._step_math)(
        variables, opt_state, {"input_ids": x}, y, jax.random.PRNGKey(0))
    rows_beside_vocab = []

    def walk(j):
        for eqn in j.eqns:
            for v in eqn.outvars:
                shape = getattr(v.aval, "shape", ())
                if len(shape) >= 2 and shape[-1] == 50:
                    rows_beside_vocab.append(int(np.prod(shape[:-1])))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert 16 in rows_beside_vocab            # the blocks' logits
    assert max(rows_beside_vocab) == 64       # the head, [d, V]


def test_perturbing_a_token_moves_nothing_before_it():
    _, model = _model()
    variables, x, _ = _seeded(model, rows=1)
    at = 29
    other = x.copy()
    other[0, at] = (other[0, at] + 7) % 50
    a, b = (_train_outputs(model, variables, ids) for ids in (x, other))
    for name in ("states", "lse", "log_exit"):
        before = np.abs(np.asarray(a[name] - b[name]))[:, :, :at]
        after = np.abs(np.asarray(a[name] - b[name]))[:, :, at:]
        assert before.max() == 0.0, name
        assert after.max() > 0.0, name


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_moves_the_reference(fault):
    """On seeded weights in float32 every fault of the configuration's
    table moves ``z_T`` far over the float32 comparison's 2e-5."""
    c, model = _model()
    variables, x, _ = _seeded(model)
    right = ref.forward(variables, x, c)
    with ref.faulty(fault):
        wrong = ref.forward(variables, x, c)
    assert _rel(wrong, right) > 1e-3, fault
    with pytest.raises(ValueError):
        with ref.faulty("no such fault"):
            pass


def test_float8_operands_read_far_over_bfloat16_ones():
    c, model = _model()
    variables, x, _ = _seeded(model)
    right = ref.forward(variables, x, c)
    with ref.operands_rounded_to(jnp.bfloat16):
        bf16 = _rel(ref.forward(variables, x, c), right)
    with ref.operands_rounded_to(jnp.float8_e4m3fn):
        fp8 = _rel(ref.forward(variables, x, c), right)
    assert 0 < bf16 < 0.05 and fp8 > 4 * bf16


# ------------------------------------------------------------------ #
# rematerialisation and the Estimator                                #
# ------------------------------------------------------------------ #
def test_remat_policy_keeps_what_the_configuration_says(monkeypatch):
    """Traced on the flash path at L = 1,024: a layer application keeps
    its input and ``LOOP_KEPT_NAMES`` -- the MLP branch's output --
    and nothing else by name, so the second forward runs the attention
    kernel again (two forward kernels and one backward a layer), and
    the passes are one ``scan``: the kernels are counted per layer, not
    per application."""
    monkeypatch.setattr(attention, "_platform", lambda q: "tpu")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        said = json.load(f)["assumed"]["rematerialisation"]
    assert lm.LOOP_KEPT_NAMES == ("mlp_out",)
    assert "mlp_out" in said and "input" in said
    module = lm.LoopedDecoderModule(
        vocab=64, hidden_size=256, n_layers=2, n_passes=3, n_head=2,
        head_dim=128, dense_width=96)
    ids = jnp.zeros((1, 1024), jnp.int32)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            ids)["params"]

    def loss(params):
        return lm.exit_weighted_loss(
            module.apply({"params": params}, ids, train=True), ids)

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    text = str(jaxpr)
    assert text.count("pallas_call[") == 2 * 3
    # named once a layer in the forward and, kept, not computed again;
    # a name outside the policy is: the second forward holds it too
    assert text.count("name=mlp_out]") == 2
    assert text.count("name=attention_q]") == 2 * 2
    named = jaxpr.pretty_print(name_stack=True)
    for scope in ("loop_body", "loop_head", "exit_loss", "attention_flash"):
        assert scope in named, scope


def _published(name):
    family = get_registry().snapshot().get(name)
    return dict((family or {"values": {}})["values"])


def test_fit_predict_and_counters(compiled_anew):
    """compile / fit / predict like the other zoo models (the step holds
    ``while`` loops: compiled anew on the virtual devices): the loss
    falls, the passes' losses and exit probabilities publish under the
    model's counters."""
    _, model = _model()
    rng = np.random.default_rng(3)
    prior = 1.0 / np.arange(1, 50) ** 1.1
    ids = rng.choice(np.arange(1, 50), size=(16, LENGTH + 1),
                     p=prior / prior.sum()).astype(np.int32)
    x, y = {"input_ids": ids[:, :-1]}, ids[:, 1:]
    model.compile(optimizer=AdamWeightDecay(lr=3e-3), seed=0)
    names = [f"zoo_model_loop_{n}_total" for n in (
        "steps", "pass_loss_millinats", "exit_probability_thousandths")]
    before = [_published(n) for n in names]
    history = model.fit((x, y), batch_size=8, epochs=3)
    assert history[-1]["loss"] < history[0]["loss"]
    logits = model.predict(x, batch_size=8)
    assert logits.shape == (16, LENGTH, 50) and logits.dtype == np.float32
    after = [_published(n) for n in names]

    def grown(i, index):
        key = f"module=,index={index}"
        return after[i][key] - before[i].get(key, 0)

    steps = grown(0, 0)
    assert steps == 3 * 2
    losses = [grown(1, t) / steps / lm.COUNT_SCALE for t in range(3)]
    exits = [grown(2, t) / steps / lm.COUNT_SCALE for t in range(3)]
    assert all(0.5 * history[-1]["loss"] < v < 1.5 * history[0]["loss"] + 1
               for v in losses), losses
    # each step's mean is rounded to a thousandth
    assert abs(sum(exits) - 1.0) < 2e-3 and min(exits) > 0
    # evaluate goes through the same loss function on z_T
    assert np.isfinite(model.evaluate((x, y), batch_size=8)["loss"])


def test_save_and_load_round_trip(tmp_path):
    _, model = _model()
    variables, x, _ = _seeded(model, rows=8)
    model.estimator.variables = variables
    model.save_model(str(tmp_path / "m"))
    loaded = LoopedDecoderLM.load_model(str(tmp_path / "m"))
    assert isinstance(loaded, LoopedDecoderLM)
    assert loaded._config["n_passes"] == 3
    a = model.predict({"input_ids": x}, batch_size=8)
    b = loaded.predict({"input_ids": x}, batch_size=8)
    assert np.array_equal(a, b)
