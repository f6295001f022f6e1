"""``ops.activations.gelu_exact``: BERT's erf GELU with a stored
derivative (ISSUE 26). The forward value is ``jax.nn.gelu``'s bit for
bit; the backward multiplies by a residual made from the forward's one
erf, so a train step evaluates erf once per element where autodiff of
the stock expression lets XLA re-derive it in every consumer."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.keras.layers import transformer
from analytics_zoo_tpu.keras.layers.transformer import TransformerBlock
from analytics_zoo_tpu.ops import activations
from analytics_zoo_tpu.ops.activations import gelu_exact


def stock(x):
    return jax.nn.gelu(x, approximate=False)


def _inputs(dtype, n=8192, seed=0):
    """|x| up to 6: both of erfc's branches and the flat tails where the
    derivative is 0 or 1."""
    rng = np.random.default_rng(seed)
    x = np.concatenate([rng.uniform(-6, 6, n - 5), [-6, -1e-3, 0, 1e-3, 6]])
    return jnp.asarray(x, dtype)


def _rel_l2(a, ref):
    a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
    return np.linalg.norm(a - ref) / np.linalg.norm(ref)


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16],
                         ids=["f32", "bf16", "f16"])
def test_forward_is_the_stock_expression_bit_for_bit(dtype, jitted):
    x = _inputs(dtype)
    wrap = jax.jit if jitted else (lambda f: f)
    want = wrap(stock)(x)
    got = wrap(gelu_exact)(x)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the value the train step sees comes from the forward rule
    under_grad, _ = wrap(lambda t: jax.vjp(gelu_exact, t))(x)
    np.testing.assert_array_equal(np.asarray(under_grad), np.asarray(want))


@pytest.mark.parametrize("cotangent", ["ones", "normal"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_gradient_against_float32_autodiff(dtype, cotangent):
    x = _inputs(dtype, seed=1)
    ct = (jnp.ones_like(x) if cotangent == "ones" else jnp.asarray(
        np.random.default_rng(2).normal(size=x.shape), dtype))
    ref = jax.vjp(stock, x.astype(jnp.float32))[1](
        ct.astype(jnp.float32))[0]
    got = jax.vjp(gelu_exact, x)[1](ct)[0]
    assert got.dtype == x.dtype
    err = _rel_l2(got, ref)
    if dtype == jnp.float32:
        assert err <= 1e-6
    elif cotangent == "ones":
        # the derivative itself: rounded once from float32 arithmetic,
        # so no worse than the stock expression's bf16 chain
        assert err <= _rel_l2(jax.vjp(stock, x)[1](ct)[0], ref) < 2 ** -8
    else:
        # a stored bf16 derivative times a bf16 cotangent is two
        # roundings of 2**-9 each (stock reads 0.0021, this 0.0022-0.0025)
        assert err <= 2 ** -8


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16],
                         ids=["f32", "bf16", "f16"])
def test_pair_crosses_the_barrier_unchanged(dtype):
    """16-bit pairs cross as one 32-bit word: every bit pattern of both
    halves comes back, sign bits and the other half's neighbours too."""
    rng = np.random.default_rng(6)
    if dtype == jnp.float32:
        a, b = (jnp.asarray(rng.normal(size=4096), dtype) for _ in "ab")
    else:
        every = jax.lax.bitcast_convert_type(
            jnp.arange(2 ** 16, dtype=jnp.uint16), dtype)
        a, b = every, every[rng.permutation(2 ** 16)]
    got_a, got_b = jax.jit(activations._made_once)(a, b)
    for got, want in ((got_a, a), (got_b, b)):
        assert got.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got).view(np.uint8),
                                      np.asarray(want).view(np.uint8))


def test_residual_is_the_derivative_alone():
    x = _inputs(jnp.bfloat16, n=256)
    _, vjp = jax.vjp(gelu_exact, x)
    (d,) = jax.tree_util.tree_leaves(vjp)
    assert d.shape == x.shape and d.dtype == x.dtype
    want = jax.vmap(jax.grad(stock))(x.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(d, np.float32), want, atol=8e-3)


def _block(activation, dtype=jnp.float32):
    return TransformerBlock(32, 2, 64, hidden_dropout=0.0,
                            attn_dropout=0.0, activation=activation,
                            dtype=dtype)


def _block_io(activation, dtype=jnp.float32):
    blk = _block(activation, dtype)
    x = jnp.asarray(
        np.random.default_rng(3).normal(size=(4, 8, 32)) * 2, jnp.float32)
    return blk, blk.init(jax.random.PRNGKey(0), x), x


def _count_primitives(jaxpr, names):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name in names
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _count_primitives(inner, names)
    return n


def test_train_step_of_a_bert_block_evaluates_erf_once():
    blk, params, x = _block_io("gelu_exact", jnp.bfloat16)

    def loss(p, t):
        return blk.apply(p, t).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params, x).jaxpr
    assert _count_primitives(jaxpr, {"erf", "erfc"}) == 1
    assert _count_primitives(jaxpr, {"optimization_barrier"}) == 1

    # what crosses from forward to backward at the hidden activation's
    # shape: ffn_out's input g (its weight gradient needs it) and the
    # stored derivative d; not ffn_in's output h, nor erf's or exp's
    _, state = blk.apply(params, x, capture_intermediates=True)
    h = state["intermediates"]["ffn_in"]["__call__"][0]
    _, vjp = jax.vjp(loss, params, x)
    hidden = [r for r in jax.tree_util.tree_leaves(vjp)
              if r.shape == h.shape]
    assert [r.dtype for r in hidden] == [jnp.bfloat16] * 2
    g = np.asarray(stock(h), np.float32)
    d = np.asarray(jax.vmap(jax.vmap(jax.vmap(jax.grad(stock))))(
        h.astype(jnp.float32)))
    got = sorted((np.asarray(r, np.float32) for r in hidden),
                 key=lambda a: np.abs(a - g).max())
    np.testing.assert_array_equal(got[0], g)
    np.testing.assert_allclose(got[1], d, atol=8e-3)
    assert all(np.abs(r - np.asarray(h, np.float32)).max() > 0.1
               for r in got)


def test_block_gradients_equal_autodiff_of_the_stock_expression(
        monkeypatch):
    blk, params, x = _block_io("gelu_exact")

    def loss(p, t):
        return jnp.sum(blk.apply(p, t) ** 2)

    val, grads = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    monkeypatch.setattr(transformer, "gelu_exact", stock)
    want_val, want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    assert float(val) == float(want_val)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def _value_and_grad(x, ct):
    out, vjp = jax.vjp(gelu_exact, x)
    return out, vjp(ct)[0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_vmap_equals_unbatched(dtype):
    """``PopulationEstimator`` vmaps whole models."""
    x = _inputs(dtype, n=512).reshape(4, 128)
    ct = jnp.asarray(np.random.default_rng(4).normal(size=x.shape), dtype)
    out, grad = jax.vmap(_value_and_grad)(x, ct)
    for i in range(x.shape[0]):
        want_out, want_grad = _value_and_grad(x[i], ct[i])
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(want_out))
        np.testing.assert_array_equal(np.asarray(grad[i]),
                                      np.asarray(want_grad))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_data_mesh_equals_one_device(devices, dtype):
    mesh = Mesh(np.array(devices[:4]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    x = _inputs(dtype, n=1024).reshape(8, 128)
    ct = jnp.asarray(np.random.default_rng(5).normal(size=x.shape), dtype)
    want_out, want_grad = jax.jit(_value_and_grad)(x, ct)
    out, grad = jax.jit(_value_and_grad, in_shardings=(rows, rows),
                        out_shardings=(rows, rows))(x, ct)
    assert len(out.sharding.device_set) == 4
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
    np.testing.assert_array_equal(np.asarray(grad), np.asarray(want_grad))


@pytest.mark.parametrize("activation,fn", [
    ("gelu_exact", stock),
    ("gelu", jax.nn.gelu),      # tanh form: GPT lineage, not touched
    ("relu", jax.nn.relu),
], ids=["gelu_exact", "gelu_tanh", "relu"])
def test_block_feeds_ffn_out_the_named_activation(activation, fn):
    blk, params, x = _block_io(activation)
    seen = {}

    def spy(next_fun, args, kwargs, context):
        out = next_fun(*args, **kwargs)
        if context.module.name in ("ffn_in", "ffn_out"):
            seen[context.module.name] = (args[0], out)
        return out

    with nn.intercept_methods(spy):
        blk.apply(params, x)
    h = seen["ffn_in"][1]
    np.testing.assert_array_equal(np.asarray(seen["ffn_out"][0]),
                                  np.asarray(fn(h)))
