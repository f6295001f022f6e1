"""Hand-written Pallas TPU flash-attention kernels (forward + backward).

The fused attention hot op the framework owns end-to-end. Blockwise
online-softmax forward: the grid walks (batch*heads, q-blocks, kv-blocks)
with the kv dimension innermost; running (max, sum, acc) live in VMEM
scratch across kv iterations, so the [L, L] score matrix never exists in
HBM. The forward also emits the per-row logsumexp, which the backward
kernels use to regenerate probabilities blockwise:

- dQ kernel: grid (BH, q-blocks, kv-blocks), accumulates
  dq_i = sum_j (p_ij * (do_i v_j^T - delta_i)) k_j in VMEM scratch;
- dK/dV kernel: grid (BH, kv-blocks, q-blocks), accumulates
  dv_j = sum_i p_ij^T do_i and dk_j = sum_i ds_ij^T q_i.

Training memory is O(L) on this kernel (saves only q, k, v, o, lse) --
the flash backward recurrence of Dao et al., re-derived for the TPU
memory hierarchy. Replaces the reference's O(L^2)-materialized attention
(ref: zoo/.../keras/layers/TransformerLayer.scala attn).

Constraints: seq % block == 0, head_dim % 64 == 0 (64 keeps the MXU at
half lane-width on the QK/PV contractions -- the same geometry every
d=64 attention pays, incl. XLA's einsum -- while 128-multiples ride it
full); callers fall back to the jnp path otherwise. Causal masking
aligns the diagonal bottom-right (tril k=lk-lq) to match
``reference_attention``; causal with len(q) > len(kv) is rejected.

The grid is declared (parallel, parallel, arbitrary) so Mosaic
pipelines the sequential kv/q accumulation dimension while batch and
row blocks schedule freely.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _auto_block(length: int, cap: int = 1024) -> int:
    """Largest 128-multiple block <= ``cap`` dividing ``length``: big
    blocks amortize the per-block VPU softmax work against the MXU
    matmuls (measured ~2.5x fwd+bwd at L=4096 vs 128-blocks) while
    staying inside VMEM (s/p tiles at [1024, 1024] f32 = 4 MB each).

    The backward kernels pass ``_bwd_cap``: 512 at d >= 128 -- they
    hold three [BQ, BK] f32 intermediates (s, p, dp) plus
    q/k/v/do/lse/delta tiles and scratch, which at 1024^2 blocks
    (~12 MB of intermediates alone) would crowd the ~16 MB per-core
    VMEM budget -- but 1024 at d <= 64 / L >= 2048, where the halved
    tiles fit and measure 6-7% faster (see _bwd_cap)."""
    for b in (1024, 896, 768, 640, 512, 384, 256, 128):
        if b <= cap and length % b == 0:
            return b
    return 128


def _causal_run(qi, ki, block_q: int, block_k: int, causal: bool,
                offset: int):
    """Whether kv-block ki overlaps the causal region of q-block qi."""
    if not causal:
        return True
    return ki * block_k <= qi * block_q + (block_q - 1) + offset


def _causal_mask(s, qi, ki, block_q: int, block_k: int, offset: int):
    """Mask scores above the bottom-right-aligned diagonal
    (reference_attention tril with k=lk-lq), so cross-length q/kv gives
    identical results on every dispatch path."""
    q_pos = qi * block_q + offset + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, causal: bool,
                      scale: float, block_q: int, block_k: int,
                      causal_offset: int, with_lse: bool):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref, (m_scr, l_scr, acc_scr) = None, rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = _causal_run(qi, ki, block_q, block_k, causal, causal_offset)

    @pl.when(run)
    def _body():
        # matmul operands stay in input dtype (bf16 rides the fast MXU
        # path; f32 accumulate via preferred_element_type) -- upcasting
        # here would silently fall to the slow full-precision MXU mode
        q = q_ref[0]                              # [BQ, D]
        k = k_ref[0]                              # [BK, D]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK]
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, causal_offset)

        m_prev = m_scr[:, :1]                     # [BQ, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                    # [BQ, BK]
        corr = jnp.exp(m_prev - m_new)            # [BQ, 1]
        l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = (m_scr[...] + jnp.log(l)).astype(lse_ref.dtype)


def _flash_fwd(q, k, v, causal: bool, scale: float, block_q: int,
               block_k: int, with_lse: bool):
    """Returns out [B,H,L,D] and, when ``with_lse``, the per-row
    logsumexp at [B*H, L, 128] (value broadcast across the 128 lanes --
    the TPU-native row-stat layout the stock flash kernel also uses;
    inference passes ``with_lse=False`` so nothing extra hits HBM)."""
    b, h, l, d = q.shape
    lk = k.shape[2]
    block_q = block_q or _auto_block(l)
    block_k = block_k or _auto_block(lk)
    if l % block_q or lk % block_k:
        raise ValueError(f"seq lens ({l},{lk}) must divide blocks "
                         f"({block_q},{block_k})")
    if d % 64:
        raise ValueError(f"head_dim {d} must be a multiple of 64")
    if causal and l > lk:
        # rows attending to nothing are undefined under flash semantics
        raise ValueError("causal attention requires len(q) <= len(kv)")
    qr = q.reshape(b * h, l, d)
    kr = k.reshape(b * h, lk, d)
    vr = v.reshape(b * h, lk, d)
    grid = (b * h, l // block_q, lk // block_k)
    out_specs = [pl.BlockSpec((1, block_q, d),
                              lambda bh, qi, ki: (bh, qi, 0))]
    out_shape = [jax.ShapeDtypeStruct((b * h, l, d), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, block_q, 128),
                                      lambda bh, qi, ki: (bh, qi, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b * h, l, 128),
                                              jnp.float32))
    res = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          causal_offset=lk - l, with_lse=with_lse),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_grid_semantics(),
        interpret=_interpret(),
    )(qr, kr, vr)
    out = res[0]
    lse = res[1] if with_lse else None
    return out.reshape(b, h, l, d), lse


def _interpret() -> bool:
    """Interpret mode is for the CPU backend only (where the tests run
    the kernel logic); every other backend compiles the kernel, so one
    that cannot says so instead of silently interpreting."""
    return jax.default_backend() == "cpu"


def _grid_semantics():
    """All three kernels iterate their LAST grid dim sequentially (the
    online-softmax / gradient accumulation over kv- or q-blocks) while
    the leading (batch*heads, row-block) dims are independent; telling
    Mosaic so lets it overlap the next block's HBM->VMEM copies with
    the current block's compute instead of assuming a serial grid."""
    if _interpret():
        return None  # interpret mode takes no TPU compiler params
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dq_scr, *, causal: bool, scale: float,
                     block_q: int, block_k: int, causal_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = _causal_run(qi, ki, block_q, block_k, causal, causal_offset)

    @pl.when(run)
    def _body():
        q = q_ref[0]                                # [BQ, D]
        k = k_ref[0]                                # [BK, D]
        v = v_ref[0]
        do = do_ref[0]                              # [BQ, D]
        lse = lse_ref[0][:, :1]                     # [BQ, 1]
        delta = delta_ref[0][:, :1]                 # [BQ, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, causal_offset)
        p = jnp.exp(s - lse)                        # [BQ, BK]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [BQ, BK]
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                      scale: float, block_q: int, block_k: int,
                      causal_offset: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = _causal_run(qi, ki, block_q, block_k, causal, causal_offset)

    @pl.when(run)
    def _body():
        q = q_ref[0]                                # [BQ, D]
        k = k_ref[0]                                # [BK, D]
        v = v_ref[0]
        do = do_ref[0]                              # [BQ, D]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, causal_offset)
        p = jnp.exp(s - lse)                        # [BQ, BK]
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [BK, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [BQ, BK]
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [BK, D]

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_cap(length: int, d: int) -> int:
    """Backward block cap: 512 keeps the three [BQ, BK] f32
    intermediates inside VMEM at d=128; at d <= 64 every q/k/v/do tile
    halves, so 1024-blocks fit AND measure 6-7% faster at L >= 2048
    (scripts/perf_flash_blocks.py) -- but only when the sequential
    grid dim keeps >= 2 steps, else Mosaic has nothing to pipeline
    and L=1024 regresses ~25%."""
    return 1024 if (d <= 64 and length >= 2048) else 512


def _flash_bwd(q, k, v, o, lse, g, causal: bool, scale: float,
               block_q: int, block_k: int):
    b, h, l, d = q.shape
    lk = k.shape[2]
    block_q = block_q or _auto_block(l, cap=_bwd_cap(l, d))
    block_k = block_k or _auto_block(lk, cap=_bwd_cap(lk, d))
    bh = b * h
    qr = q.reshape(bh, l, d)
    kr = k.reshape(bh, lk, d)
    vr = v.reshape(bh, lk, d)
    dor = g.reshape(bh, l, d)
    # delta_i = rowsum(do_i * o_i): one fused elementwise pass, O(L*D)
    delta = jnp.sum(dor.astype(jnp.float32) *
                    o.reshape(bh, l, d).astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (bh, l, 128))
    common = dict(causal=causal, scale=scale, block_q=block_q,
                  block_k=block_k, causal_offset=lk - l)
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh_, a, b_: (bh_, a, 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda bh_, a, b_: (bh_, b_, 0))
    row_spec = pl.BlockSpec((1, block_q, 128),
                            lambda bh_, a, b_: (bh_, a, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, **common),
        grid=(bh, l // block_q, lk // block_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh_, a, b_: (bh_, a, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, l, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_grid_semantics(),
        interpret=_interpret(),
    )(qr, kr, vr, dor, lse, delta)

    # dk/dv walk kv-blocks in the outer grid dim with q innermost; the
    # index maps swap (a, b_) roles relative to the dq kernel
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda bh_, a, b_: (bh_, b_, 0))
    k_spec2 = pl.BlockSpec((1, block_k, d), lambda bh_, a, b_: (bh_, a, 0))
    row_spec2 = pl.BlockSpec((1, block_q, 128),
                             lambda bh_, a, b_: (bh_, b_, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, **common),
        grid=(bh, lk // block_k, l // block_q),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, row_spec2,
                  row_spec2],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh_, a, b_: (bh_, a, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, a, b_: (bh_, a, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, lk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_grid_semantics(),
        interpret=_interpret(),
    )(qr, kr, vr, dor, lse, delta)
    return (dq.reshape(b, h, l, d), dk.reshape(b, h, lk, d),
            dv.reshape(b, h, lk, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def pallas_flash_attention_fwd(q, k, v, causal: bool = False,
                               scale: Optional[float] = None,
                               block_q: Optional[int] = None,
                               block_k: Optional[int] = None):
    """Flash attention on [B, H, L, D]; exact softmax attention.
    ``block_q``/``block_k`` default to the largest 128-multiple divisor
    of each sequence length, capped at 1024."""
    out, _ = _flash_fwd(q, k, v, causal, _resolve_scale(scale, q),
                        block_q, block_k, with_lse=False)
    return out


def _resolve_scale(scale, q) -> float:
    return scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])


def _vjp_fwd(q, k, v, causal, scale, block_q, block_k):
    s = _resolve_scale(scale, q)
    out, lse = _flash_fwd(q, k, v, causal, s, block_q, block_k,
                          with_lse=True)
    return out, (q, k, v, out, lse, s)


def _vjp_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse, s = res
    return _flash_bwd(q, k, v, out, lse, g, causal, s, block_q, block_k)


pallas_flash_attention_fwd.defvjp(_vjp_fwd, _vjp_bwd)
