"""The short-row attention kernels (``ops/pallas_short_attention.py``,
PR 36) in Pallas interpret mode on the CPU: forward and gradients
against ``reference_attention``, the entry points that reach them, the
partitioned lowering on virtual devices, and the layer across the two
paths."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.keras.layers.transformer import MultiHeadSelfAttention
from analytics_zoo_tpu.ops import attention
from analytics_zoo_tpu.ops.pallas_short_attention import (
    MAX_SEQ, _heads_a_step, pallas_short_attention)

D = 64


def _heads_first(t, heads):
    b, l, _ = t.shape
    return t.reshape(b, l, heads, D).transpose(0, 2, 1, 3)


def _reference(q, k, v, heads):
    """``reference_attention`` in float32 on the packed layout."""
    out = attention.reference_attention(*(
        _heads_first(t.astype(jnp.float32), heads) for t in (q, k, v)))
    b, _, l, _ = out.shape
    return out.transpose(0, 2, 1, 3).reshape(b, l, heads * D)


def _operands(b, l, heads, dtype, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, l, heads * D), dtype)
                 for _ in range(4))


# the blockwise kernel's tests hold float32 to 2e-5 (forward) and 2e-4
# (gradients): so do these; bfloat16 to a few of its roundings
TOLERANCE = {jnp.float32: (2e-5, 2e-4), jnp.bfloat16: (1e-2, 3e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,l,heads", [
    (1, 128, 2), (4, 128, 12), (4, 384, 2), (1, 384, 12),
    (1, 512, 2), (1, 512, 12), (4, 256, 4),
])
def test_forward_and_gradients_match_reference(b, l, heads, dtype):
    q, k, v, ct = _operands(b, l, heads, dtype)
    ct = ct.astype(jnp.float32)
    fwd_tol, grad_tol = TOLERANCE[dtype]

    def ours(q, k, v):
        out = pallas_short_attention(q, k, v, heads)
        return jnp.sum(out.astype(jnp.float32) * ct), out

    def reference(q, k, v):
        out = _reference(q, k, v, heads)
        return jnp.sum(out * ct), out

    (_, out), grads = jax.value_and_grad(ours, (0, 1, 2), has_aux=True)(
        q, k, v)
    (_, want), want_grads = jax.value_and_grad(
        reference, (0, 1, 2), has_aux=True)(q, k, v)
    assert out.dtype == dtype and out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=fwd_tol)
    for got, exact, name in zip(grads, want_grads, "qkv"):
        assert got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(exact, np.float32),
            atol=grad_tol, err_msg=f"d{name}")


def test_head_groups_write_their_own_columns(monkeypatch):
    """More heads than a step walks (16 heads at 512 are two groups of
    8; here 8 heads in steps of 2 and of 4): each group reads and
    writes its own columns."""
    from analytics_zoo_tpu.ops import pallas_short_attention as kernels

    q, k, v, ct = _operands(2, 128, 8, jnp.float32, seed=7)
    want = jax.grad(lambda *a: jnp.sum(_reference(*a, 8) * ct),
                    (0, 1, 2))(q, k, v)
    for heads_a_step in (2, 4):
        monkeypatch.setattr(kernels, "_STEP_ROWS", heads_a_step * 128)
        jax.clear_caches()      # ``_call`` is jitted, the rows read inside
        assert kernels._heads_a_step(128, 8) == heads_a_step
        got = jax.grad(lambda *a: jnp.sum(
            pallas_short_attention(*a, 8) * ct), (0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=2e-4)
    jax.clear_caches()


def test_scale_reaches_both_kernels():
    q, k, v, ct = _operands(2, 128, 2, jnp.float32, seed=1)

    def heads_first_reference(q, k, v):
        out = attention.reference_attention(
            *(_heads_first(t, 2) for t in (q, k, v)), scale=0.3)
        return jnp.sum(out.transpose(0, 2, 1, 3).reshape(q.shape) * ct)

    got = jax.grad(lambda *a: jnp.sum(
        pallas_short_attention(*a, 2, 0.3) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(heads_first_reference, (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4)


@pytest.mark.parametrize("shape,heads,match", [
    ((1, 128, 192), 3, "even number of heads"),     # an odd head count
    ((1, 128, 256), 2, "even number of heads"),     # heads of 128
    ((1, 640, 128), 2, "multiple of 128 up to"),
    ((1, 96, 128), 2, "multiple of 128 up to"),
])
def test_kernel_refuses_what_the_rule_refuses(shape, heads, match):
    x = jnp.zeros(shape, jnp.float32)
    with pytest.raises(ValueError, match=match):
        pallas_short_attention(x, x, x, heads)
    b, l, width = shape
    assert attention.attention_path(
        "tpu", l, l, width // heads, heads, heads) != "flash_short"


def test_heads_a_step_is_a_function_of_the_shapes():
    # whole lane tiles (pairs of heads) that divide the heads
    assert _heads_a_step(384, 12) == 12         # BERT-base: one step a row
    assert _heads_a_step(MAX_SEQ, 12) == 12
    assert _heads_a_step(MAX_SEQ, 16) == 8      # BERT-large at 512
    assert _heads_a_step(256, 16) == 16
    assert _heads_a_step(128, 2) == 2
    assert _heads_a_step(MAX_SEQ, 26) == 2      # 13 pairs: no divisor fits


@pytest.fixture()
def on_chip_rule(monkeypatch):
    """The dispatcher told it runs off the CPU: the rule answers as on
    the chip, the kernels still run interpreted."""
    monkeypatch.setattr(attention, "_platform", lambda q: "tpu")


def test_both_entries_reach_the_kernel(on_chip_rule):
    """``packed_attention`` hands the kernels its operands as they are;
    ``dot_product_attention`` keeps its heads-first contract round the
    same kernels. Both equal the einsum path."""
    heads = 4
    q, k, v, _ = _operands(2, 128, heads, jnp.float32, seed=2)
    packed = attention.packed_attention(q, k, v, heads)
    want = _reference(q, k, v, heads)
    np.testing.assert_allclose(np.asarray(packed), np.asarray(want),
                               atol=2e-5)
    first = attention.dot_product_attention(
        *(_heads_first(t, heads) for t in (q, k, v)))
    np.testing.assert_allclose(
        np.asarray(first), np.asarray(_heads_first(want, heads)), atol=2e-5)

    def lowered(fn, *args):
        return jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)

    text = lowered(lambda *a: attention.packed_attention(*a, heads), q, k, v)
    assert set(re.findall(r"/(attention_[a-z_]+)/", text)) == {
        "attention_flash_short"}
    assert "stablehlo.transpose" not in text     # operands read in place
    # a mask keeps the path it had, between the heads-first transposes
    mask = jnp.ones((2, 128), jnp.int32)
    text = lowered(lambda *a: attention.packed_attention(
        *a, heads, key_padding_mask=mask), q, k, v)
    assert set(re.findall(r"/(attention_[a-z_]+)/", text)) == {
        "attention_einsum"}


def _compiled_grad(fn, *args):
    return jax.jit(jax.grad(fn, argnums=(0, 1, 2, 3))).lower(*args).compile()


def test_partitions_over_the_batch(devices, on_chip_rule):
    """Four virtual devices, the batch sharded over ``data``, the
    weights replicated, one GSPMD program: told the mesh, the step
    round the new entry gathers no attention operand (each device runs
    the kernels on its own rows), keeps the caller's scopes on the
    kernels' operations, and equals the one-device result."""
    heads, b, l = 4, 8, 128
    mesh = Mesh(np.array(devices[:4]), ("data",))
    rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    rng = np.random.RandomState(3)
    w = jnp.asarray(rng.randn(heads * D, heads * D) * 0.05, jnp.float32)
    q, k, v = _operands(b, l, heads, jnp.float32, seed=4)[:3]

    def loss(w, q, k, v, mesh=mesh):
        with jax.named_scope("layer"):
            out = attention.packed_attention(q @ w, k @ w, v @ w, heads,
                                             mesh=mesh)
        return jnp.sum((out @ w) ** 2)

    sharded = [jax.device_put(w, whole)] + [
        jax.device_put(t, rows) for t in (q, k, v)]
    compiled = _compiled_grad(loss, *sharded)
    text = compiled.as_text()
    assert "all-gather" not in text and "all-to-all" not in text
    assert "collective-permute" not in text
    # the weight gradient's sum over the batch is the one collective
    assert "all-reduce" in text
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any("/jvp(layer)/attention_flash_short/" in n for n in names)
    assert any("/transpose(jvp(layer))/attention_flash_short/" in n
               for n in names)
    got = compiled(*sharded)
    assert got[1].sharding.is_equivalent_to(rows, 3)     # dq stays sharded
    want = jax.jit(jax.grad(lambda *a: loss(*a, mesh=None),
                            argnums=(0, 1, 2, 3)))(w, q, k, v)
    for g, x in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x),
                                   rtol=2e-4, atol=2e-4)
    # untold, the partitioner can only gather the kernels' operands
    untold = _compiled_grad(lambda *a: loss(*a, mesh=None), *sharded)
    assert "all-gather" in untold.as_text()


def test_partitions_over_head_pairs_too(devices, on_chip_rule):
    """Batch over ``data`` and head pairs over ``model`` (the megatron
    layout of the fused projection): each device runs its own rows and
    pairs, still without a collective round the kernels; an axis that
    does not divide is left whole."""
    heads, b, l = 4, 4, 128
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("data", "model"))
    sharding = NamedSharding(mesh, P("data", None, "model"))
    q, k, v, ct = (jax.device_put(t, sharding)
                   for t in _operands(b, l, heads, jnp.float32, seed=5))

    def loss(q, k, v, ct):
        return jnp.sum(attention.packed_attention(
            q, k, v, heads, mesh=mesh) * ct)

    compiled = _compiled_grad(loss, q, k, v, ct)
    assert not re.search(r"all-gather|all-reduce|all-to-all|"
                         r"collective-permute", compiled.as_text())
    got = compiled(q, k, v, ct)
    want = jax.grad(lambda q, k, v: jnp.sum(_reference(q, k, v, heads) * ct),
                    (0, 1, 2))(q, k, v)
    for g, x in zip(got, want):
        assert g.sharding.is_equivalent_to(sharding, 3)
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), atol=2e-4)
    # three rows over data=2, one pair over model=2: neither divides
    odd = tuple(t[:3, :, :2 * D] for t in (q, k, v))
    np.testing.assert_allclose(
        np.asarray(attention.packed_attention(*odd, 2, mesh=mesh)),
        np.asarray(_reference(*odd, 2)), atol=2e-5)


def test_estimator_tells_the_layer_its_mesh(devices):
    """``Estimator`` traces its model calls under ``traced_under`` of its
    own mesh, whatever the context's: the layer reads it back."""
    import flax.linen as nn
    import optax

    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.parallel.mesh import create_mesh, traced_mesh

    seen = []

    class Probe(nn.Module):
        @nn.compact
        def __call__(self, x):
            seen.append(traced_mesh())
            return nn.Dense(2)(x)

    mesh = create_mesh({"data": 2}, devices=devices[:2])
    est = Estimator(Probe(), loss=lambda p, t: jnp.mean((p - t) ** 2),
                    optimizer=optax.sgd(0.1), mesh=mesh)
    x = np.ones((4, 3), np.float32)
    y = np.zeros((4, 2), np.float32)
    assert traced_mesh() is None
    est.fit((x, y), batch_size=4, epochs=1)
    est.evaluate((x, y), batch_size=4)
    est.predict(x, batch_size=4)
    assert traced_mesh() is None
    told = [m for m in seen if m is not None]
    assert len(told) >= 3 and all(m == mesh for m in told)


@pytest.mark.parametrize("dtype,tolerance", [
    (jnp.float32, 2e-4), (jnp.bfloat16, 3e-2)])
def test_layer_agrees_across_the_two_paths(monkeypatch, dtype, tolerance):
    """``MultiHeadSelfAttention`` at heads of 64: output and parameter
    gradients through the short-row kernels against the einsum path
    (the CPU's), to the compute dtype's tolerance."""
    layer = MultiHeadSelfAttention(hidden_size=2 * D, n_head=2, dtype=dtype)
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(2, 128, 2 * D), jnp.float32)
    ct = jnp.asarray(rng.randn(2, 128, 2 * D), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), x)

    def run():
        def loss(params):
            out = layer.apply({"params": params}, x)
            return jnp.sum(out.astype(jnp.float32) * ct), out

        (_, out), grads = jax.value_and_grad(loss, has_aux=True)(
            variables["params"])
        text = jax.jit(loss).trace(variables["params"]).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
        return out, grads, set(re.findall(r"/(attention_[a-z_]+)/", text))

    out_einsum, grads_einsum, scopes = run()
    assert scopes == {"attention_einsum"}
    monkeypatch.setattr(attention, "_platform", lambda q: "tpu")
    out_short, grads_short, scopes = run()
    assert scopes == {"attention_flash_short"}
    np.testing.assert_allclose(np.asarray(out_short, np.float32),
                               np.asarray(out_einsum, np.float32),
                               atol=tolerance)
    flat_short = jax.tree_util.tree_leaves_with_path(grads_short)
    flat_einsum = jax.tree_util.tree_leaves(grads_einsum)
    for (path, got), want in zip(flat_short, flat_einsum):
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(
            np.asarray(got) / scale, np.asarray(want) / scale,
            atol=tolerance, err_msg=jax.tree_util.keystr(path))
