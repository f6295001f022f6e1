"""The gated delta rule of Gated DeltaNet (Yang, Kautz, Hatamizadeh,
arXiv:2412.06464) in its chunked form, and the causal short convolution
that runs before it. The first scan in the tree.

Per head, with a state ``S`` of [d_k, d_v] and, at each position t,
a key ``k_t`` (L2-normalised), a query ``q_t``, a value ``v_t``, a
log-decay ``g_t <= 0`` and a write strength ``beta_t`` in (0, 1):

    S_t  = exp(g_t) S_{t-1}
    S_t += k_t (beta_t (v_t - S_t^T k_t))^T
    o_t  = S_t^T q_t

That sequential recurrence is the definition
(``benchmark/reference/qwen3_next.delta_rule`` and the tests hold it).
``chunked_gated_delta_rule`` computes the same thing a chunk of ``C``
positions at a time (the WY / UT form): with ``G`` the cumulative
log-decay inside a chunk, ``Gamma_ij = exp(G_i - G_j)`` for ``j <= i``,

    A  = strictly_lower(diag(beta) K K^T * Gamma),  T = (I + A)^-1
    W  = T diag(beta) (K * e^G),   U = T diag(beta) V
    V' = U - W S                          (each position's written value)
    O  = (Q * e^G) S + (Q K^T * Gamma) V'          (diagonal included)
    S <- e^{G_C} S + (K * e^{G_C - G})^T V'

Everything that does not read ``S`` is computed for all chunks at once;
a ``lax.scan`` over the chunks carries ``S`` alone. ``T`` is solved by
forward substitution in float32: ``T_i = e_i - sum_{j<i} A_ij T_j``
row by row inside diagonal blocks of 16, then block rows joined by
``T_ij = -T_ii A_i,<i T_<i,<i`` (FLA's ``solve_tril``). Every term is
bounded (``T`` is the WY form of a product of contractions), so nothing
cancels; a power series for ``(I + A)^-1`` is no substitute, its terms
grow as binomials where decay is weak and keys aligned, the regime of
trained heads. Its gradient is ``-T^T dT T^T``, in float32 too. The
state and the log-decays are float32; every other product takes its
operands in the inputs' dtype and accumulates in float32. The backward
pass is JAX's own through the scan (a decoder layer's ``nn.remat``
computes the forward again for it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["chunked_gated_delta_rule", "causal_short_conv"]

_F32 = jnp.float32


def causal_short_conv(x, w):
    """Depthwise causal convolution over time: x [B, L, C], w [K, C] ->
    [B, L, C] with ``out[t] = sum_i w[i] x[t - K + 1 + i]``, positions
    before 0 reading zeros (``w[K - 1]`` weighs the position itself).
    Accumulated in float32, returned in x's dtype; the padded copy stays
    in x's dtype (widened before it, forward and backward took 1.8 x
    the time on the chip: PERF.md, PR 40)."""
    width, length = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(padded[:, i:i + length].astype(_F32) * w[i].astype(_F32)
              for i in range(width))
    return out.astype(x.dtype)


def _dot(spec, a, b, dtype):
    """``einsum`` of operands cast to ``dtype``, float32 result."""
    precision = jax.lax.Precision.HIGHEST if dtype == _F32 else None
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      precision=precision, preferred_element_type=_F32)


_BLOCK = 16


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` [..., C, C] strictly lower triangular,
    float32, by forward substitution (module docstring)."""
    size = a.shape[-1]
    block = _BLOCK if size % _BLOCK == 0 else size
    n = size // block
    diag = jnp.stack([a[..., i * block:(i + 1) * block,
                        i * block:(i + 1) * block] for i in range(n)], -3)
    inv = jnp.broadcast_to(jnp.eye(block, dtype=_F32), diag.shape)
    for i in range(1, block):        # row i reads the finished rows < i
        row = -jnp.sum(diag[..., i, :, None] * inv, axis=-2)
        inv = inv.at[..., i, :].add(row)
    t = inv[..., 0, :, :]
    for i in range(1, n):
        t_ii = inv[..., i, :, :]
        rows = a[..., i * block:(i + 1) * block, :i * block]
        left = -_dot("...ij,...jk->...ik", t_ii,
                     _dot("...ij,...jk->...ik", rows, t, _F32), _F32)
        zeros = jnp.zeros(t.shape[:-1] + (block,), _F32)
        t = jnp.concatenate([jnp.concatenate([t, zeros], -1),
                             jnp.concatenate([left, t_ii], -1)], -2)
    return t


def _unit_lower_inverse_fwd(a):
    t = _unit_lower_inverse(a)
    return t, t


def _unit_lower_inverse_bwd(t, dt):
    da = -_dot("...ji,...jk->...ik", t,
               _dot("...ij,...kj->...ik", dt, t, _F32), _F32)
    return (jnp.tril(da, -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def chunked_gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """q, k [B, L, H, d_k] (k L2-normalised and q scaled as the caller
    wants them), v [B, L, H, d_v], g and beta [B, L, H] -> o [B, L, H,
    d_v] in v's dtype (module docstring), from a zero state. ``chunk``
    must divide L."""
    b, length, h, d_k = q.shape
    d_v = v.shape[-1]
    if length % chunk:
        raise ValueError(f"chunk {chunk} does not divide length {length}")
    n = length // chunk
    dtype = v.dtype

    def chunks(x):                       # [B, L, H, ...] -> [B, H, N, C, ...]
        x = x.reshape((b, n, chunk, h) + x.shape[3:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v = chunks(q), chunks(k), chunks(v)
    log_decay = jnp.cumsum(chunks(g.astype(_F32)), axis=-1)     # G [B,H,N,C]
    beta = chunks(beta.astype(_F32))
    row = jnp.arange(chunk)
    causal = row[:, None] >= row[None, :]
    # Gamma, zero above the diagonal (the exponent is masked first, so
    # no overflow and no NaN in the gradient)
    gamma = jnp.exp(jnp.where(
        causal, log_decay[..., :, None] - log_decay[..., None, :], -jnp.inf))
    a = jnp.where(row[:, None] > row[None, :],
                  beta[..., None] * _dot("...id,...jd->...ij", k, k, dtype)
                  * gamma, 0.0)
    t = _unit_lower_inverse(a.astype(_F32))
    w = _dot("...ij,...jd->...id", t,
             k * (beta * jnp.exp(log_decay))[..., None], dtype)
    u = _dot("...ij,...jd->...id", t, v * beta[..., None], dtype)
    qk = _dot("...id,...jd->...ij", q, k, dtype) * gamma
    q_decayed = q * jnp.exp(log_decay)[..., None]
    last = log_decay[..., -1:]
    k_decayed = k * jnp.exp(last - log_decay)[..., None]

    def step(state, at):
        w_n, u_n, q_n, qk_n, k_n, last_n = at
        written = u_n - _dot("...ij,...jd->...id", w_n, state, dtype)
        out = (_dot("...ij,...jd->...id", q_n, state, dtype)
               + _dot("...ij,...jd->...id", qk_n, written, dtype))
        state = (jnp.exp(last_n)[..., None] * state
                 + _dot("...ci,...cj->...ij", k_n, written, dtype))
        return state, out.astype(dtype)

    by_chunk = [jnp.moveaxis(x, 2, 0) for x in
                (w, u, q_decayed, qk, k_decayed, last)]
    _, out = jax.lax.scan(step, jnp.zeros((b, h, d_k, d_v), _F32), by_chunk)
    # [N, B, H, C, d_v] -> [B, L, H, d_v]
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, length, d_v)
    out = jnp.moveaxis(out, 1, 2)
    return out
