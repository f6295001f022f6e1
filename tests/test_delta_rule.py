"""The chunked gated delta rule (``ops/delta_rule.py``) against its
definition, the token-by-token recurrence of
``benchmark/reference/qwen3_next.delta_rule``, in float32, forward and
backward; and the causal short convolution against ``jnp.convolve``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops.delta_rule import (
    _chunk_recurrence, _unit_lower_inverse, causal_short_conv,
    chunked_gated_delta_rule)
from benchmark.reference.qwen3_next import delta_rule


def _inputs(length, heads, d_k=16, d_v=12, rows=2, seed=0):
    """q, k (L2-normalised), v, g <= 0 and beta in (0, 1), float32."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(rows, length, heads, d_k)) / np.sqrt(d_k)
    k = rng.normal(size=(rows, length, heads, d_k))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(rows, length, heads, d_v))
    g = -np.abs(rng.normal(0, 0.5, size=(rows, length, heads)))
    beta = 1 / (1 + np.exp(-rng.normal(size=(rows, length, heads))))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("heads", [1, 3])
@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("length", [64, 192, 256])
def test_chunked_form_is_the_recurrence(length, chunk, heads):
    """Outputs and the gradients of all five inputs under a
    non-linear loss, to 1e-5."""
    args = _inputs(length, heads)

    def chunked(*a):
        return chunked_gated_delta_rule(*a, chunk=chunk)

    def sequential(*a):
        return delta_rule(*a)[0]

    with jax.default_matmul_precision("highest"):
        assert _rel(chunked(*args), sequential(*args)) < 1e-5
        grads = [jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                          argnums=range(5))(*args)
                 for f in (chunked, sequential)]
    for got, want in zip(*grads):
        assert _rel(got, want) < 1e-5


def test_no_decay_and_no_write_leave_the_state_alone():
    """g -> 0 and beta -> 0 after a first stretch: nothing decays and
    nothing is written, so every later position reads the state the
    first stretch left (the recurrence's last state there)."""
    q, k, v, g, beta = _inputs(128, 2)
    g = g.at[:, 48:].set(0.0)
    beta = beta.at[:, 48:].set(0.0)
    with jax.default_matmul_precision("highest"):
        out = chunked_gated_delta_rule(q, k, v, g, beta, chunk=32)
        _, state = delta_rule(*(a[:, :48] for a in (q, k, v, g, beta)))
        read = jnp.einsum("rlhk,rhkv->rlhv", q[:, 48:], state)
    np.testing.assert_allclose(out[:, 48:], read, rtol=1e-5, atol=1e-5)


def _aligned_weak_decay(length=256, heads=2, d_k=32, d_v=16, cos=0.75):
    """The regime of trained heads: decay near 0 (g ~ -1e-3), beta near
    0.9, and L2-normed keys that share one direction per head (cosine
    cos^2 = 0.56 between two of them)."""
    rng = np.random.default_rng(7)
    common = rng.normal(size=(heads, d_k))
    common /= np.linalg.norm(common, axis=-1, keepdims=True)
    k = rng.normal(size=(1, length, heads, d_k))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    k = cos * common + np.sqrt(1 - cos ** 2) * k
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = rng.normal(size=(1, length, heads, d_k)) / np.sqrt(d_k)
    v = rng.normal(size=(1, length, heads, d_v))
    g = -1e-3 * np.abs(rng.normal(size=(1, length, heads)))
    beta = 0.9 + 0.05 * np.tanh(rng.normal(size=(1, length, heads)))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


@pytest.mark.parametrize("dtype,bound", [("float32", 1e-5),
                                         ("bfloat16", 0.02)])
def test_weak_decay_and_aligned_keys_stay_on_the_recurrence(dtype, bound):
    """Chunks of 64 where (I + A)^-1 as a power series cancels
    catastrophically (its terms reach ~1e9; PR 40's first form read
    3.5e3 here in float32, inf in bfloat16): forward substitution stays
    on the recurrence, in float32 to 1e-5 and with the program's
    bfloat16 operands within 0.02 (bfloat16-rounded inputs alone move
    the recurrence by ~0.004)."""
    args = _aligned_weak_decay()
    with jax.default_matmul_precision("highest"):
        want = delta_rule(*args)[0]
        low = [a.astype(dtype) for a in args[:3]] + list(args[3:])
        got = chunked_gated_delta_rule(*low, chunk=64)
    assert jnp.all(jnp.isfinite(got.astype(jnp.float32)))
    assert _rel(got.astype(jnp.float32), want) < bound


def test_weak_decay_and_aligned_keys_gradients_match():
    """Every input's gradient in that regime, float32, to 1e-5."""
    args = _aligned_weak_decay(length=128)

    def loss(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a)))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda *a: chunked_gated_delta_rule(
            *a, chunk=64)), argnums=range(5))(*args)
        want = jax.grad(loss(lambda *a: delta_rule(*a)[0]),
                        argnums=range(5))(*args)
    for got_i, want_i in zip(got, want):
        assert _rel(got_i, want_i) < 1e-5


def test_weak_decay_and_aligned_keys_bfloat16_gradients_stay_near():
    """Every input's gradient in that regime with the program's
    bfloat16 operands against the float32 recurrence's, within the
    forward's 0.02 (bfloat16-rounded inputs alone move the recurrence's
    gradients by ~0.004-0.011 here); the float32 case is the test
    above."""
    args = _aligned_weak_decay(length=128)

    def loss(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a).astype(jnp.float32)))

    low = [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])
    got = jax.grad(loss(lambda *a: chunked_gated_delta_rule(
        *a, chunk=64)), argnums=range(5))(*low)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(lambda *a: delta_rule(*a)[0]),
                        argnums=range(5))(*args)
    for got_i, want_i in zip(got, want):
        got_i = got_i.astype(jnp.float32)
        assert jnp.all(jnp.isfinite(got_i))
        assert _rel(got_i, want_i) < 0.02


def _chunk_scan(w, u, q, qk, k, decay):
    """What the recurrence kernels compute, as a scan over the chunks:
    per chunk ``V' = U - W S``, ``O = Q S + QK V'``,
    ``S <- decay S + K^T V'``, from a zero state."""
    b, h, _, _, d_k = w.shape

    def step(state, at):
        w_n, u_n, q_n, qk_n, k_n, decay_n = at
        written = u_n - w_n @ state
        out = q_n @ state + qk_n @ written
        state = (decay_n[..., None, None] * state
                 + jnp.swapaxes(k_n, -1, -2) @ written)
        return state, out

    by_chunk = [jnp.moveaxis(x, 2, 0) for x in (w, u, q, qk, k, decay)]
    _, out = jax.lax.scan(
        step, jnp.zeros((b, h, d_k, u.shape[-1]), jnp.float32), by_chunk)
    return jnp.moveaxis(out, 0, 2)


@pytest.mark.parametrize("n", [1, 5])
@pytest.mark.parametrize("heads", [1, 3, 20])
@pytest.mark.parametrize("chunk", [16, 64])
def test_recurrence_kernels_are_the_chunk_scan(chunk, heads, n):
    """The forward kernel and the backward kernel alone against a scan
    of the same chunk step and JAX's derivative of it, float32, to
    1e-5: one chunk and several, one head block and (20 heads) two of
    10, every one of the six inputs' gradients."""
    rng = np.random.default_rng(chunk + heads + n)
    d_k, d_v = 16, 12

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.normal(0, scale, (2, heads, n) + shape),
                           jnp.float32)

    args = (normal(chunk, d_k, scale=0.25), normal(chunk, d_v),
            normal(chunk, d_k, scale=0.25), normal(chunk, chunk, scale=0.1),
            normal(chunk, d_k, scale=0.25),
            jnp.asarray(rng.uniform(0.3, 1.0, (2, heads, n)), jnp.float32))
    cotangent = normal(chunk, d_v)
    with jax.default_matmul_precision("highest"):
        got, got_vjp = jax.vjp(_chunk_recurrence, *args)
        want, want_vjp = jax.vjp(_chunk_scan, *args)
        assert got.shape == want.shape and got.dtype == jnp.float32
        assert _rel(got, want) < 1e-5
        for got_i, want_i in zip(got_vjp(cotangent), want_vjp(cotangent)):
            # from a zero state a single chunk's dW, dQ and decay
            # gradient are zero: the bound is relative but admits 0 = 0
            assert got_i.shape == want_i.shape
            assert (jnp.linalg.norm(got_i - want_i)
                    <= 1e-5 * jnp.linalg.norm(want_i))


@pytest.mark.parametrize("chunks", [(1,), (3,), (2, 65)])
@pytest.mark.parametrize("size", [16, 64, 24])
def test_unit_lower_inverse_is_the_inverse(size, chunks):
    """The kernel's forward substitution against numpy's float64
    inverse, and its gradient against JAX's through ``jnp.linalg.inv``,
    for chunk counts that do not fill the lane block (1, 3, 130)."""
    rng = np.random.default_rng(size)
    a = jnp.asarray(np.tril(rng.uniform(-1, 1, chunks + (size, size)), -1),
                    jnp.float32)
    eye = jnp.eye(size)
    with jax.default_matmul_precision("highest"):
        got = _unit_lower_inverse(a)
        want = np.linalg.inv(np.asarray(eye + a, np.float64))
        weights = jnp.asarray(rng.normal(size=(size, size)), jnp.float32)
        grad = jax.grad(lambda x: jnp.sum(_unit_lower_inverse(x) * weights))
        want_grad = jax.grad(
            lambda x: jnp.sum(jnp.linalg.inv(eye + x) * weights))
        assert got.shape == a.shape and got.dtype == jnp.float32
        assert _rel(got, want) < 1e-5
        assert _rel(grad(a), jnp.tril(want_grad(a), -1)) < 1e-5


def test_unit_lower_inverse_where_decay_is_weak_and_keys_aligned():
    """The kernel on the A of ``_aligned_weak_decay``'s chunks of 64,
    where a power series cancels: float32 substitution against numpy's
    float64 inverse of the same float32 A, to 1e-5."""
    _, k, _, g, beta = (np.asarray(x, np.float64)
                        for x in _aligned_weak_decay())
    chunk = 64

    def chunks(x):                      # [1, L, H, ...] -> [H, N, C, ...]
        x = x[0].reshape((-1, chunk) + x.shape[2:])
        return np.moveaxis(x, 2, 0)

    k, g, beta = chunks(k), np.cumsum(chunks(g), -1), chunks(beta)
    a = np.tril(beta[..., None] * (k @ np.swapaxes(k, -1, -2))
                * np.exp(g[..., :, None] - g[..., None, :]), -1)
    a = a.astype(np.float32)
    want = np.linalg.inv(np.eye(chunk) + a.astype(np.float64))
    # the series' terms A^k reach ~1e9 here; T's entries stay within 1
    assert np.abs(np.linalg.matrix_power(a, 16)).max() > 1e6
    assert _rel(_unit_lower_inverse(jnp.asarray(a)), want) < 1e-5


def test_chunk_must_divide_the_length():
    with pytest.raises(ValueError, match="does not divide"):
        chunked_gated_delta_rule(*_inputs(96, 1), chunk=64)


def test_bfloat16_operands_stay_near_the_recurrence():
    """The program's precision: bf16 operands, float32 state."""
    args = _inputs(256, 2)
    low = [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])
    with jax.default_matmul_precision("highest"):
        want = delta_rule(*args)[0]
    got = chunked_gated_delta_rule(*low, chunk=64)
    assert got.dtype == jnp.bfloat16
    assert _rel(got.astype(jnp.float32), want) < 0.02


@pytest.mark.parametrize("width", [1, 4])
def test_short_conv_is_causal_convolution_per_channel(width):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 23, 5)).astype(np.float32)
    w = rng.normal(size=(width, 5)).astype(np.float32)
    got = causal_short_conv(jnp.asarray(x), jnp.asarray(w))
    for r in range(2):
        for c in range(5):
            want = np.convolve(x[r, :, c], w[::-1, c])[:23]
            np.testing.assert_allclose(got[r, :, c], want, rtol=1e-5,
                                       atol=1e-5)
    # position t never reads t + 1
    later = x.copy()
    later[:, 10:] += 1.0
    moved = causal_short_conv(jnp.asarray(later), jnp.asarray(w))
    np.testing.assert_array_equal(moved[:, :10], got[:, :10])
