"""The gated delta rule of Gated DeltaNet (Yang, Kautz, Hatamizadeh,
arXiv:2412.06464) in its chunked form, and the causal short convolution
that runs before it.

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

Everything that does not read ``S`` is computed for all chunks at once.
The chunks' serial part, the last three lines, is a Pallas kernel pair
(``_recurrence_kernel``, ``_recurrence_bwd_kernel``) over a grid of
(rows, blocks of heads, chunks) with the chunks innermost and in order:
``S`` is float32 in a VMEM scratch buffer across all the chunks of a
block of heads and leaves it only as the state entering each chunk,
written for the backward (docs/kernels.md "The delta rule's chunk
recurrence").

``T`` is solved by forward substitution in float32, ``T_i = e_i -
sum_{j<i} A_ij T_j`` row by row, in one Pallas kernel
(``_substitution_kernel``) that puts the chunks on the 128 lanes and a
chunk's rows and columns on the leading and sublane axes: every row
update is a multiply-add over full vregs (docs/kernels.md "The delta
rule's triangular solve"). Every term is bounded (``T`` is the WY form
of a product of contractions), so nothing cancels; a power series for
``(I + A)^-1`` is no substitute, its terms grow as binomials where decay
is weak and keys aligned, the regime of trained heads. Its gradient is
``-T^T dT T^T``, in float32 too. The state and the log-decays are
float32; every other product takes its operands in the inputs' dtype and
accumulates in float32. The backward of the chunk-parallel part is
JAX's; that of the serial part is the backward kernel, which walks the
chunks last to first with the state's gradient in VMEM and computes each
chunk's written values again from the stored state (a decoder layer's
``nn.remat`` computes the forward again for it, with the states; its
first forward writes none).
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


# heads a grid step of the chunk recurrence: 16 of the cell's 32 heads,
# so a layer is 2 x 128 steps, each step's fixed cost spread over 16
# heads' products (docs/kernels.md "The delta rule's chunk recurrence")
_HEADS = 16


def _head_decay(decay_ref, h):
    """[1, 1]: head ``h``'s ``e^{G_C}`` from the step's [1, heads] row."""
    row = decay_ref[0, 0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == h, row, 0.0), axis=1, keepdims=True)


def _recurrence_kernel(w_ref, u_ref, q_ref, qk_ref, k_ref, decay_ref, o_ref,
                       *refs):
    """One chunk of a block of heads. Per head, from the state ``S``
    entering the chunk (float32, in the VMEM scratch, zero at the first
    chunk): ``V' = U - W S``, ``O = Q S + QK V'``,
    ``S <- e^{G_C} S + K^T V'``. ``refs`` is ``(states_ref, s_scr)``
    where the backward wants ``S`` as each chunk found it, else
    ``(s_scr,)``."""
    *states_ref, s_scr = refs
    dtype, size = w_ref.dtype, w_ref.shape[-2]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    # the heads unrolled (the body traced once), so that one head's
    # products fill the gaps in another's chain: rolled, the forward was
    # 0.6 ms a layer slower (v5e)
    def head(h, carry):
        s = s_scr[h]
        if states_ref:
            states_ref[0][0, h, 0] = s
        wq = _dot("ij,jd->id",
                  jnp.concatenate([w_ref[0, h, 0], q_ref[0, h, 0]]), s, dtype)
        written = u_ref[0, h, 0] - wq[:size]
        out = wq[size:] + _dot("ij,jd->id", qk_ref[0, h, 0], written, dtype)
        o_ref[0, h, 0] = out.astype(o_ref.dtype)
        s_scr[h] = (_head_decay(decay_ref, h) * s
                    + _dot("ci,cj->ij", k_ref[0, h, 0], written, dtype))
        return carry

    jax.lax.fori_loop(0, s_scr.shape[0], head, 0, unroll=True)


def _recurrence_bwd_kernel(w_ref, u_ref, q_ref, qk_ref, k_ref, decay_ref,
                           s_ref, do_ref, dw_ref, du_ref, dq_ref, dqk_ref,
                           dk_ref, ddecay_ref, ds_scr):
    """The forward kernel's derivatives for one chunk of a block of
    heads, the chunks walked last to first. ``ds_scr`` holds ``dS``, the
    gradient of the state leaving the chunk (float32, zero after the
    last chunk); ``V'`` is computed again from the stored ``S``:
    ``dV' = QK^T dO + K dS`` (which is ``dU``), ``dW = -dV' S^T``,
    ``dQ = dO S^T``, ``dQK = dO V'^T``, ``dK = V' dS^T``,
    ``d e^{G_C} = sum(S * dS)``, then ``dS <- e^{G_C} dS + Q^T dO -
    W^T dV'``."""
    dtype, size = w_ref.dtype, w_ref.shape[-2]

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    lane = jax.lax.broadcasted_iota(jnp.int32, ddecay_ref.shape[-2:], 1)

    def head(h, ddecay):
        s, ds, do = s_ref[0, h, 0], ds_scr[h], do_ref[0, h, 0]
        w, q, k = w_ref[0, h, 0], q_ref[0, h, 0], k_ref[0, h, 0]
        written = u_ref[0, h, 0] - _dot("ij,jd->id", w, s, dtype)
        dwritten = (_dot("ji,jd->id", qk_ref[0, h, 0], do, dtype)
                    + _dot("id,de->ie", k, ds, dtype))
        low = dwritten.astype(dtype)
        dq_dw = _dot("ie,de->id", jnp.concatenate([do, -low]), s, dtype)
        dq_ref[0, h, 0] = dq_dw[:size].astype(dq_ref.dtype)
        dw_ref[0, h, 0] = dq_dw[size:].astype(dw_ref.dtype)
        du_ref[0, h, 0] = dwritten
        dqk_ref[0, h, 0] = _dot("ie,je->ij", do, written,
                                dtype).astype(dqk_ref.dtype)
        dk_ref[0, h, 0] = _dot("ie,de->id", written, ds,
                               dtype).astype(dk_ref.dtype)
        total = jnp.sum(jnp.sum(s * ds, axis=0, keepdims=True), axis=1,
                        keepdims=True)
        ds_scr[h] = (_head_decay(decay_ref, h) * ds
                     + _dot("ci,cj->ij", jnp.concatenate([q, -w]),
                            jnp.concatenate([do, low]), dtype))
        return ddecay + jnp.where(lane == h, total, 0.0)

    ddecay_ref[0, 0, 0] = jax.lax.fori_loop(
        0, ds_scr.shape[0], head, jnp.zeros(lane.shape, _F32), unroll=True)


def _heads_a_step(heads: int) -> int:
    """The most heads, up to ``_HEADS``, that divide ``heads``."""
    return max(n for n in range(1, min(heads, _HEADS) + 1) if heads % n == 0)


def _vmem_bytes(shape, dtype) -> int:
    """VMEM bytes of a block: lanes padded to 128, sublanes to the
    dtype's tile."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, cols = shape
    tile = 32 // itemsize
    padded = -(-rows // tile) * tile * -(-cols // 128) * 128
    return math.prod(lead) * padded * itemsize


def _by_step(x, heads: int):
    """[B, H, N] -> [B, N, H / heads, 1, heads]: a grid step's decays
    as one row."""
    b, h, n = x.shape
    return jnp.transpose(x.reshape(b, h // heads, heads, n),
                         (0, 3, 1, 2))[:, :, :, None]


def _from_step(x):
    """The inverse of ``_by_step``."""
    b, n, groups, _, heads = x.shape
    return jnp.transpose(x[:, :, :, 0], (0, 2, 3, 1)).reshape(
        b, groups * heads, n)


@functools.partial(jax.jit, static_argnums=(8, 9, 10), inline=True)
def _recurrence(w, u, q, qk, k, decay, states, do, keep: bool,
                backward: bool, interpret: bool):
    """One kernel over the grid (rows, head blocks, chunks), the chunks
    innermost and in order, last to first for the backward. ``w``, ``u``,
    ``q``, ``qk``, ``k`` are [B, H, N, C, ...], each read at its chunk in
    place, ``decay`` [B, H, N]. The forward (``states`` and ``do`` None)
    returns ``o`` [B, H, N, C, d_v], with ``keep`` also the float32
    states entering each chunk [B, H, N, d_k, d_v]; the backward the six
    gradients. Jitted and inlined: every GDN layer makes the same calls,
    and each kernel is traced for the first alone."""
    b, h, n, size, d_k = w.shape
    d_v = u.shape[-1]
    heads = _heads_a_step(h)

    def chunk(c):
        return n - 1 - c if backward else c

    def spec(*tail):
        return pl.BlockSpec((1, heads, 1) + tail,
                            lambda i, g, c: (i, g, chunk(c), 0, 0))

    operands = [w, u, q, qk, k, _by_step(decay, heads)]
    in_specs = [spec(size, d_k), spec(size, d_v), spec(size, d_k),
                spec(size, size), spec(size, d_k),
                pl.BlockSpec((1, 1, 1, 1, heads),
                             lambda i, g, c: (i, chunk(c), g, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, h, n, size, d_v), w.dtype)]
    out_specs = [spec(size, d_v)]
    kernel = _recurrence_kernel
    if backward:
        operands += [states, do]
        in_specs += [spec(d_k, d_v), spec(size, d_v)]
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)
                     for x in operands[:6]]
        out_specs = in_specs[:6]
        kernel = _recurrence_bwd_kernel
    elif keep:
        out_shape.append(jax.ShapeDtypeStruct((b, h, n, d_k, d_v), _F32))
        out_specs.append(spec(d_k, d_v))
    scratch = pltpu.VMEM((heads, d_k, d_v), _F32)
    # every block twice (double-buffered), and the state
    blocks = sum(_vmem_bytes((heads,) + s.block_shape[3:], x.dtype)
                 for s, x in zip(in_specs + out_specs, operands + out_shape))
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=(pallas_attention._VMEM_SLACK + 2 * blocks
                          + _vmem_bytes(scratch.shape, _F32)))
    out = pl.pallas_call(
        kernel,
        grid=(b, h // heads, n),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[scratch],
        compiler_params=params,
        interpret=interpret,
        name="delta_rule_recurrence_bwd" if backward
        else "delta_rule_recurrence",
    )(*operands)
    if backward:
        return (*out[:5], _from_step(out[5]))
    return out if keep else out[0]


@jax.custom_vjp
def _chunk_recurrence(w, u, q, qk, k, decay):
    """The chunks' serial part: ``w``, ``q``, ``qk``, ``k`` [B, H, N, C,
    ...] in the inputs' dtype, ``u`` [B, H, N, C, d_v] and ``decay`` =
    ``e^{G_C}`` [B, H, N] float32 -> ``o`` [B, H, N, C, d_v] in the
    inputs' dtype, from a zero state held in VMEM across the chunks."""
    return _recurrence(w, u, q, qk, k, decay, None, None, False, False,
                       pallas_attention._interpret())


def _chunk_recurrence_fwd(*args):
    o, states = _recurrence(*args, None, None, True, False,
                            pallas_attention._interpret())
    return o, (*args, states)


def _chunk_recurrence_bwd(residuals, do):
    return _recurrence(*residuals, do, False, True,
                       pallas_attention._interpret())


_chunk_recurrence.defvjp(_chunk_recurrence_fwd, _chunk_recurrence_bwd,
                         optimize_remat=True)


def chunked_gated_delta_rule(q, k, v, g, beta, chunk: int = 64):
    """q, k [B, L, H, d_k] (k L2-normalised and q scaled as the caller
    wants them), v [B, L, H, d_v], g and beta [B, L, H] -> o [B, L, H,
    d_v] in v's dtype (module docstring), from a zero state. ``chunk``
    must divide L."""
    b, length, h, _ = q.shape
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
    # u enters a subtraction and stays float32; the rest enter products
    # alone and arrive as those products' operands
    out = _chunk_recurrence(w.astype(dtype), u, q_decayed.astype(dtype),
                            qk.astype(dtype), k_decayed.astype(dtype),
                            jnp.exp(last[..., 0]))
    # [B, H, N, C, d_v] -> [B, L, H, d_v]
    return jnp.moveaxis(out.reshape(b, h, length, d_v), 1, 2)
