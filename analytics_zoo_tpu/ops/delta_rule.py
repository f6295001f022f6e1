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
forward substitution in float32, ``T_i = e_i - sum_{j<i} A_ij T_j`` row
by row, in one Pallas kernel (``_substitution_kernel``) that puts the
chunks on the 128 lanes and a chunk's rows and columns on the leading
and sublane axes: every row update is a multiply-add over full vregs
(docs/kernels.md "The delta rule's triangular solve"). Every term is
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

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops import pallas_attention

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


# chunks a grid step, two lane tiles: at the cell's [64, 64] chunks
# 0.79 ms a layer against 1.01 with one tile (v5e, transposes included)
_LANES = 256
_SUBLANES = 8


def _substitution_kernel(a_ref, t_ref):
    """Forward substitution for a block of chunks on the lanes:
    ``a_ref[j, i]`` holds ``A_ij`` and ``t_ref[i, k]`` receives ``T_ik``,
    each a row of ``_LANES`` chunks. Row ``i`` is ``e_i - sum_{j<i} A_ij
    T_j``; the finished rows ``j < i`` are zero from column ``j + 1``
    on, so the sum runs over the first ``i`` columns alone (rounded up
    to the sublane tile)."""
    size, _, lanes = t_ref.shape
    column = jax.lax.broadcasted_iota(jnp.int32, (size, lanes), 0)
    for i in range(size):
        unit = (column == i).astype(_F32)
        width = min(size, -(-i // _SUBLANES) * _SUBLANES)
        if width == 0:
            t_ref[i] = unit
            continue

        def update(j, acc, i=i, width=width):
            return acc + a_ref[j, i:i + 1, :] * t_ref[j, :width, :]

        acc = jax.lax.fori_loop(0, i, update,
                                jnp.zeros((width, lanes), _F32))
        if width < size:
            acc = jnp.concatenate(
                [acc, jnp.zeros((size - width, lanes), _F32)])
        t_ref[i] = unit - acc


@functools.partial(jax.jit, static_argnums=1, inline=True)
def _solve(lanes, interpret: bool):
    """The kernel over ``lanes`` [C, C, chunks] (chunks a multiple of
    ``_LANES``). Jitted and inlined: every GDN layer, in the forward and
    in the second forward, makes the same call, and the kernel's body
    (a loop a row) is traced for the first of them alone."""
    size, _, padded = lanes.shape
    spec = pl.BlockSpec((size, size, _LANES), lambda n: (0, 0, n))
    # one float32 block, sublanes padded; A's and T's, two buffers each
    block = 4 * size * -(-size // _SUBLANES) * _SUBLANES * _LANES
    return pl.pallas_call(
        _substitution_kernel,
        grid=(padded // _LANES,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((size, size, padded), _F32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=pallas_attention._VMEM_SLACK + 4 * block),
        interpret=interpret,
        name="unit_lower_inverse",
    )(lanes)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` [..., C, C] strictly lower triangular,
    float32, by forward substitution in one Pallas kernel with the
    chunks on the lanes (module docstring)."""
    size = a.shape[-1]
    count = math.prod(a.shape[:-2])
    padded = -(-count // _LANES) * _LANES
    # [chunk, i, j] -> [j, i, chunk]; padding chunks (A = 0) solve to I
    lanes = jnp.pad(jnp.transpose(a.reshape(count, size, size), (2, 1, 0)),
                    ((0, 0), (0, 0), (0, padded - count)))
    t = _solve(lanes, pallas_attention._interpret())
    # [i, k, chunk] -> [chunk, i, k]
    return jnp.transpose(t[..., :count], (2, 0, 1)).reshape(a.shape)


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
