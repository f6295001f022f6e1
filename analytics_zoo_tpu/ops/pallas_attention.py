"""Hand-written Pallas TPU flash-attention kernels (forward + backward).

The fused attention hot op the framework owns end-to-end. Blockwise
online-softmax forward: the grid walks (batch*heads, q-blocks, kv-blocks)
with the kv dimension innermost; running (max, sum, acc) live in VMEM
scratch across kv iterations, so the [L, L] score matrix never exists in
HBM. The forward also emits the per-row logsumexp, from which the
backward regenerates each block's probabilities, once:

- one backward kernel: grid (KV heads, query heads of the group, the
  (kv-block, q-block) pairs that hold an allowed score); per pair
  s = q k^T, the mask, p = exp(s - lse), dp = do v^T and
  ds = p (dp - delta) feed dv_j += p^T do_i, dk_j += ds^T q_i and
  dq_i += ds k_j: five MXU products. dq of the current query head and
  dk, dv of the KV head are whole-sequence float32 accumulators in
  VMEM, so the call asks for the VMEM they need
  (``_fused_bwd_vmem_bytes``);
- past ``FUSED_BWD_VMEM_BUDGET`` (d = 128 in bfloat16: beyond ~20k) a dQ
  kernel, grid (BH, q-blocks, kv-blocks), and a dK/dV kernel, grid
  (BH, kv-blocks, q-blocks), hold only blocks and each regenerate the
  scores: seven products a pair. ``flash_backward_path`` is the rule.

Training memory is O(L) on this kernel (saves only q, k, v, o, lse) --
the flash backward recurrence of Dao et al., re-derived for the TPU
memory hierarchy. Replaces the reference's O(L^2)-materialized attention
(ref: zoo/.../keras/layers/TransformerLayer.scala attn).

Constraints: seq % block == 0, head_dim % 64 == 0 (a 64-deep
contraction and a 64-wide result each fill half of a 128 x 128 MXU
pass, here with tiles that fill 64 of 128 lanes; 128-multiples ride it
full); callers fall back to the jnp path otherwise. These kernels are
the dispatcher's for sequences longer than ``attention.FLASH_MIN_SEQ``;
up to it plain self-attention with heads of 64 goes to
``pallas_short_attention``, which pays the same half-filled passes but
reads two heads a 128-lane tile from the projection's own layout and
holds a head's whole row in one tile (1.04 ms against this kernel's
2.09 a BERT-base layer at L384, forward + backward: docs/kernels.md
"Measured crossover"). Causal masking
aligns the diagonal bottom-right (tril k=lk-lq) to match
``reference_attention``; causal with len(q) > len(kv) is rejected.

Two variants ride the same kernels (docs/kernels.md):

- **window** (``window=W``, causal only): row i reads keys
  ``i - W < j <= i``. The forward's sequential grid dimension is
  *relative*: it has only as many steps as the widest run of blocks
  any row block needs (``_steps``), step t of row block qi is block
  ``lo(qi) + t`` (``_kv_bounds``), and steps past ``hi(qi)`` neither
  compute nor fetch (their index map stays on block ``hi``). A plain
  causal call uses the same bounds with ``lo = 0``, so the blocks above
  the diagonal are not fetched either. The one-kernel backward walks a
  list of exactly the pairs inside the bounds (``_pair_walk``).
- **grouped KV heads**: K and V may carry fewer heads than Q
  (``h % h_kv == 0``); query head n reads KV head ``n // (h / h_kv)``
  through the index maps, so K/V are never repeated in HBM. The
  backward walks the query heads of a KV head's group in a sequential
  grid dimension and sums their dK/dV in VMEM.

A third caller composes them: ``pallas_eva_attention`` (EVA: exact
keys inside a window and chunk summaries of the earlier windows under
one softmax) is the plain causal kernel over the windows counted as
heads, one rectangular call over the summaries a later window, and a
join by logsumexp; its backward hands each part the joint output and
logsumexp (docs/kernels.md "EVA").

The forward's grid is declared (parallel, parallel, arbitrary) so Mosaic
pipelines the sequential kv accumulation dimension while batch and row
blocks schedule freely; the backward's is (parallel, arbitrary,
arbitrary): its accumulators outlive all but the KV head.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _auto_block(length: int, cap: int = 1024) -> int:
    """Largest 128-multiple block <= ``cap`` dividing ``length``: big
    blocks amortize the per-block VPU softmax work against the MXU
    matmuls (measured ~2.5x fwd+bwd at L=4096 vs 128-blocks). The
    forward at [1024, 1024] holds s and p tiles of 4 MB each in float32,
    ~11 MB with its operand tiles: inside the 16 MiB of VMEM a kernel
    gets when it asks for none (the chip has 128 MiB). The backward
    chooses by ``_bwd_blocks`` and asks for what it needs."""
    for b in (1024, 896, 768, 640, 512, 384, 256, 128):
        if b <= cap and length % b == 0:
            return b
    return 128


def _kv_bounds(qi, block_q: int, block_k: int, nk: int, causal: bool,
               offset: int, window: Optional[int], lo_of=max, hi_of=min):
    """First and last kv-block (inclusive) that q-block ``qi`` reads.
    Works on Python ints (``_steps``) and, with ``lo_of=jnp.maximum``,
    ``hi_of=jnp.minimum``, on program ids."""
    lo, hi = 0, nk - 1
    if causal:
        hi = hi_of(nk - 1, (qi * block_q + block_q - 1 + offset) // block_k)
    if window is not None:
        lo = lo_of(qi * block_q + offset - window + 1, 0) // block_k
    return lo, hi


def _q_bounds(ki, block_q: int, block_k: int, nq: int, causal: bool,
              offset: int, window: Optional[int], lo_of=max, hi_of=min):
    """The inverse: first and last q-block that reads kv-block ``ki``.
    With len(q) < len(kv) the window can put the oldest kv-blocks out of
    every row's reach: then ``hi`` is -1 (floor division) and no step
    runs."""
    lo, hi = 0, nq - 1
    if causal:
        lo = lo_of(ki * block_k - offset, 0) // block_q
    if window is not None:
        hi = hi_of(nq - 1, (ki * block_k + block_k + window - 2 - offset)
                   // block_q)
    return lo, hi


def _steps(bounds, n_outer: int, **geometry) -> int:
    """Length of the sequential grid dimension: the widest run of
    blocks any outer block needs."""
    return max(hi - lo + 1 for lo, hi in
               (bounds(i, **geometry) for i in range(n_outer)))


def _traced(bounds, i, **geometry):
    return bounds(i, lo_of=jnp.maximum, hi_of=jnp.minimum, **geometry)


def _mask(s, qi, ki, block_q: int, block_k: int, causal: bool,
          offset: int, window: Optional[int]):
    """Mask scores above the bottom-right-aligned diagonal
    (reference_attention tril with k=lk-lq), so cross-length q/kv gives
    identical results on every dispatch path, and scores ``window`` or
    more keys behind it. A row whose every key in an edge block is
    masked adds exp(0) terms under a running max of NEG_INF; the first
    block with a real score (the diagonal's, at the latest) rescales
    them by exp(NEG_INF - m) = 0."""
    if not causal:
        return s
    q_pos = qi * block_q + offset + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = q_pos >= k_pos
    if window is not None:
        keep = keep & (q_pos - k_pos < window)
    return jnp.where(keep, s, NEG_INF)


def _keys(k_ref, ks_ref):
    """The block's keys [BK, D]: with a shared head, its columns follow
    the head's own (joined in VMEM; HBM holds the shared head once)."""
    if ks_ref is None:
        return k_ref[0]
    return jnp.concatenate([k_ref[0], ks_ref[0]], axis=-1)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, scale: float,
                      with_lse: bool, shared: bool, geometry: dict):
    ks_ref, rest = (rest[0], rest[1:]) if shared else (None, rest)
    o_ref, rest = rest[0], rest[1:]
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref, (m_scr, l_scr, acc_scr) = None, rest
    qi = pl.program_id(1)
    step = pl.program_id(2)
    lo, hi = _traced(_kv_bounds, qi, **geometry)
    ki = lo + step

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki <= hi)
    def _body():
        # matmul operands stay in input dtype (bf16 rides the fast MXU
        # path; f32 accumulate via preferred_element_type) -- upcasting
        # here would silently fall to the slow full-precision MXU mode
        q = q_ref[0]                              # [BQ, D]
        k = _keys(k_ref, ks_ref)                  # [BK, D]
        v = v_ref[0]                              # [BK, Dv]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK]
        s = _mask(s, qi, ki, **_mask_geometry(geometry))

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

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = (m_scr[...] + jnp.log(l)).astype(lse_ref.dtype)


def _mask_geometry(geometry: dict) -> dict:
    return {k: geometry[k] for k in
            ("block_q", "block_k", "causal", "offset", "window")}


def _check(q, k, v, causal: bool, window: Optional[int],
           k_shared=None) -> int:
    """Validates the shapes; returns query heads per KV head."""
    h, l, d = q.shape[1:]
    h_kv, lk = k.shape[1:3]
    d_keys = k.shape[-1] + (0 if k_shared is None else k_shared.shape[-1])
    if d % 64 or v.shape[-1] % 64 or k.shape[-1] % 64:
        raise ValueError(f"head_dims {d} (q), {k.shape[-1]} (k), "
                         f"{v.shape[-1]} (v) must be multiples of 64")
    if d_keys != d:
        raise ValueError(f"keys are {d_keys} wide, queries {d}")
    if k_shared is not None and (k_shared.shape[1] != 1 or h_kv != h):
        raise ValueError("a shared key is one head beside a key head "
                         "for every query head")
    if causal and l > lk:
        # rows attending to nothing are undefined under flash semantics
        raise ValueError("causal attention requires len(q) <= len(kv)")
    if window is not None and (not causal or window < 1):
        raise ValueError("a window needs causal=True and window >= 1")
    if h % h_kv:
        raise ValueError(f"{h} query heads do not divide over {h_kv} "
                         "KV heads")
    return h // h_kv


def _flash_fwd(q, k, v, causal: bool, scale: float, block_q: int,
               block_k: int, with_lse: bool, window: Optional[int] = None,
               k_shared=None):
    """Returns out [B,H,L,Dv] and, when ``with_lse``, the per-row
    logsumexp at [B*H, L, 128] (value broadcast across the 128 lanes --
    the TPU-native row-stat layout the stock flash kernel also uses;
    inference passes ``with_lse=False`` so nothing extra hits HBM)."""
    b, h, l, d = q.shape
    h_kv, lk, d_k = k.shape[1:]
    d_v = v.shape[-1]
    group = _check(q, k, v, causal, window, k_shared)
    block_q = block_q or _auto_block(l)
    block_k = block_k or _auto_block(lk)
    if l % block_q or lk % block_k:
        raise ValueError(f"seq lens ({l},{lk}) must divide blocks "
                         f"({block_q},{block_k})")
    qr = q.reshape(b * h, l, d)
    kr = k.reshape(b * h_kv, lk, d_k)
    vr = v.reshape(b * h_kv, lk, d_v)
    geometry = dict(block_q=block_q, block_k=block_k, nk=lk // block_k,
                    causal=causal, offset=lk - l, window=window)
    grid = (b * h, l // block_q, _steps(_kv_bounds, l // block_q,
                                        **geometry))

    def q_map(bh, qi, step):
        return bh, qi, 0

    def kv_map(bh, qi, step, heads=group):
        lo, hi = _traced(_kv_bounds, qi, **geometry)
        return bh // heads, jnp.minimum(lo + step, hi), 0

    in_specs = [
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_k, d_k), kv_map),
        pl.BlockSpec((1, block_k, d_v), kv_map),
    ]
    operands = [qr, kr, vr]
    if k_shared is not None:
        # one head a batch row, read by every query head of the row
        in_specs.append(pl.BlockSpec(
            (1, block_k, d - d_k), functools.partial(kv_map, heads=h)))
        operands.append(k_shared.reshape(b, lk, d - d_k))
    out_specs = [pl.BlockSpec((1, block_q, d_v), q_map)]
    out_shape = [jax.ShapeDtypeStruct((b * h, l, d_v), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, block_q, 128), q_map))
        out_shape.append(jax.ShapeDtypeStruct((b * h, l, 128),
                                              jnp.float32))
    res = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=scale,
                          with_lse=with_lse, shared=k_shared is not None,
                          geometry=geometry),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(*operands)
    out = res[0]
    lse = res[1] if with_lse else None
    return out.reshape(b, h, l, d_v), lse


def _interpret() -> bool:
    """Interpret mode is for the CPU backend only (where the tests run
    the kernel logic); every other backend compiles the kernel, so one
    that cannot says so instead of silently interpreting."""
    return jax.default_backend() == "cpu"


# Mosaic's own scratch beside the buffers a kernel declares.
_VMEM_SLACK = 4 * 2 ** 20


def _compiler_params(semantics=("parallel", "parallel", "arbitrary"),
                     **params):
    """The forward and the two block-wise backward kernels iterate their
    LAST grid dim sequentially (the online-softmax / gradient
    accumulation over kv- or q-blocks) while the leading (batch*heads,
    row-block) dims are independent; telling Mosaic so lets it overlap
    the next block's HBM->VMEM copies with the current block's compute
    instead of assuming a serial grid. The one-kernel backward names its
    own semantics and the VMEM it needs."""
    if _interpret():
        return None  # interpret mode takes no TPU compiler params
    return pltpu.CompilerParams(dimension_semantics=semantics, **params)


def _probs_and_ds(q, k, v, do, lse, delta, qi, ki, scale, geometry):
    """The block's probabilities and score gradients, regenerated from
    the saved row logsumexp (one body for every backward kernel)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s = _mask(s, qi, ki, **_mask_geometry(geometry))
    p = jnp.exp(s - lse)                            # [BQ, BK]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)         # [BQ, BK]
    return p, p * (dp - delta) * scale


def _write_dkv(dk_scr, dv_scr, dk_ref, dv_ref, dks_ref):
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
    if dks_ref is None:
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
    else:
        d_k = dk_ref.shape[-1]
        dk_ref[0] = dk_scr[:, :d_k].astype(dk_ref.dtype)
        dks_ref[0] = dk_scr[:, d_k:]


def _bwd_in_specs(geometry: dict, d: int, d_k: int, d_v: int, q_map,
                  kv_map, shared_map=None) -> list:
    """Block specs of a backward kernel's operands (q, k, v, do, lse,
    delta and, with ``shared_map``, the shared key head) under its
    index maps."""
    block_q, block_k = geometry["block_q"], geometry["block_k"]
    row_spec = pl.BlockSpec((1, block_q, 128), q_map)
    specs = [pl.BlockSpec((1, block_q, d), q_map),
             pl.BlockSpec((1, block_k, d_k), kv_map),
             pl.BlockSpec((1, block_k, d_v), kv_map),
             pl.BlockSpec((1, block_q, d_v), q_map), row_spec, row_spec]
    if shared_map is not None:
        specs.append(pl.BlockSpec((1, block_k, d - d_k), shared_map))
    return specs


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     *rest, scale: float, shared: bool, geometry: dict):
    ks_ref, (dq_ref, dq_scr) = (rest[0], rest[1:]) if shared else (None, rest)
    qi = pl.program_id(1)
    step = pl.program_id(2)
    lo, hi = _traced(_kv_bounds, qi, **geometry)
    ki = lo + step

    @pl.when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(ki <= hi)
    def _body():
        k = _keys(k_ref, ks_ref)                    # [BK, D]
        _, ds = _probs_and_ds(
            q_ref[0], k, v_ref[0], do_ref[0], lse_ref[0][:, :1],
            delta_ref[0][:, :1], qi, ki, scale, geometry)
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *rest, scale: float, q_steps: int, shared: bool,
                      geometry: dict, q_geometry: dict):
    """One KV head's block ``ki``; the sequential dimension walks the
    query heads of the group, and for each the q-blocks that read this
    kv-block. With a shared key head, ``dk_scr`` holds both parts and
    the shared columns go out per head (summed over the heads by the
    caller)."""
    if shared:
        ks_ref, dk_ref, dv_ref, dks_ref, dk_scr, dv_scr = rest
    else:
        ks_ref, dks_ref = None, None
        dk_ref, dv_ref, dk_scr, dv_scr = rest
    ki = pl.program_id(1)
    step = pl.program_id(2)
    lo, hi = _traced(_q_bounds, ki, **q_geometry)
    qi = lo + step % q_steps

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(qi <= hi)
    def _body():
        q = q_ref[0]                                # [BQ, D]
        do = do_ref[0]                              # [BQ, Dv]
        p, ds = _probs_and_ds(
            q, _keys(k_ref, ks_ref), v_ref[0], do, lse_ref[0][:, :1],
            delta_ref[0][:, :1], qi, ki, scale, geometry)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [BK, Dv]
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [BK, D]

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        _write_dkv(dk_scr, dv_scr, dk_ref, dv_ref, dks_ref)


def _flash_bwd_split(operands, dq_shape, dkv_shape, scale: float,
                     group: int, h: int, geometry: dict, q_geometry: dict):
    """The backward as two kernels that hold only blocks, for sequences
    whose whole-sequence accumulators pass ``FUSED_BWD_VMEM_BUDGET``:
    dQ walks (head, q-block, kv-blocks), dK/dV walks (KV head, kv-block,
    group's heads x q-blocks); each regenerates the scores."""
    shared = len(dkv_shape) == 3
    block_q, block_k = geometry["block_q"], geometry["block_k"]
    (bh, l, d), (bh_kv, lk, d_k) = dq_shape.shape, dkv_shape[0].shape
    d_v = dkv_shape[1].shape[-1]
    nq, nk = l // block_q, lk // block_k

    def q_map(bh_, qi, step):
        return bh_, qi, 0

    def kv_map(bh_, qi, step, heads=group):
        lo, hi = _traced(_kv_bounds, qi, **geometry)
        return bh_ // heads, jnp.minimum(lo + step, hi), 0

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, shared=shared,
                          geometry=geometry),
        grid=(bh, nq, _steps(_kv_bounds, nq, **geometry)),
        in_specs=_bwd_in_specs(
            geometry, d, d_k, d_v, q_map, kv_map,
            functools.partial(kv_map, heads=h) if shared else None),
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(*operands)

    q_steps = _steps(_q_bounds, nk, **q_geometry)

    def q_map2(bh_, ki, step):
        lo, hi = _traced(_q_bounds, ki, **q_geometry)
        return (bh_ * group + step // q_steps,
                jnp.maximum(jnp.minimum(lo + step % q_steps, hi), 0), 0)

    def kv_map2(bh_, ki, step):
        return bh_, ki, 0

    return [dq] + list(pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, q_steps=q_steps,
                          shared=shared, geometry=geometry,
                          q_geometry=q_geometry),
        grid=(bh_kv, nk, group * q_steps),
        in_specs=_bwd_in_specs(
            geometry, d, d_k, d_v, q_map2, kv_map2,
            (lambda bh_, ki, step: (bh_ // h, ki, 0)) if shared else None),
        out_specs=[pl.BlockSpec((1, block_k) + s.shape[2:], kv_map2)
                   for s in dkv_shape],
        out_shape=dkv_shape,
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d_v), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(*operands))


def _flash_bwd_kernel(ki_ref, qi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, *rest, scale: float, shared: bool,
                      geometry: dict):
    """One (kv-block, q-block) pair of one query head: the scores are
    regenerated once and feed all three gradients. The grid walks (KV
    head, query head of its group, the pairs that hold an allowed score:
    ``_pair_walk``'s two tables, read from SMEM); ``dq`` of the current
    query head and ``dk``/``dv`` of the KV head live in float32 for the
    whole sequence in VMEM, so ``dq`` outlives the kv-blocks and
    ``dk``/``dv`` the group's heads. With a shared key head ``dk_scr``
    holds both parts and the shared columns go out per head in float32
    (summed over the heads by the caller)."""
    if shared:
        (ks_ref, dq_ref, dk_ref, dv_ref, dks_ref,
         dq_scr, dk_scr, dv_scr) = rest
    else:
        ks_ref, dks_ref = None, None
        dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = rest
    head, pair = pl.program_id(1), pl.program_id(2)
    block_q, block_k = geometry["block_q"], geometry["block_k"]
    ki, qi = ki_ref[pair], qi_ref[pair]

    @pl.when((pair == 0) & (head == 0))
    def _init_kv_head():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(pair == 0)
    def _init_query_head():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0]                                    # [BQ, D]
    k = _keys(k_ref, ks_ref)                        # [BK, D]
    do = do_ref[0]                                  # [BQ, Dv]
    p, ds = _probs_and_ds(
        q, k, v_ref[0], do, lse_ref[0][:, :1], delta_ref[0][:, :1],
        qi, ki, scale, geometry)
    ds = ds.astype(q.dtype)
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
    keys = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
    dv_scr[keys, :] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # [BK, Dv]
    dk_scr[keys, :] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # [BK, D]
    dq_scr[rows, :] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # [BQ, D]

    last = pair == pl.num_programs(2) - 1

    @pl.when(last)
    def _finish_query_head():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)

    @pl.when(last & (head == pl.num_programs(1) - 1))
    def _finish_kv_head():
        _write_dkv(dk_scr, dv_scr, dk_ref, dv_ref, dks_ref)


def _pair_walk(nk: int, q_geometry: dict):
    """The (kv-block, q-block) pairs that hold an allowed score, as two
    tables in the order the one-kernel backward walks them: kv-blocks
    outermost, so K and V are fetched once a run of pairs."""
    pairs = []
    for ki in range(nk):
        lo, hi = _q_bounds(ki, **q_geometry)
        pairs += [(ki, qi) for qi in range(lo, hi + 1)]
    return tuple(jnp.asarray(np.asarray(t, np.int32)) for t in zip(*pairs))


def _flash_bwd_fused(operands, dq_shape, dkv_shape, scale: float,
                     group: int, h: int, geometry: dict, q_geometry: dict):
    """The backward as one kernel over ``_pair_walk``'s pairs, with the
    whole-sequence accumulators and output blocks that
    ``_fused_bwd_vmem_bytes`` counts and the call asks VMEM for."""
    shared = len(dkv_shape) == 3
    block_q, block_k = geometry["block_q"], geometry["block_k"]
    (_, l, d), (bh_kv, lk, d_k) = dq_shape.shape, dkv_shape[0].shape
    d_v = dkv_shape[1].shape[-1]

    def q_map(kv_head, head, pair, ki_ref, qi_ref):
        return kv_head * group + head, qi_ref[pair], 0

    def kv_map(kv_head, head, pair, ki_ref, qi_ref, heads=1):
        return kv_head // heads, ki_ref[pair], 0

    def whole_q(kv_head, head, pair, ki_ref, qi_ref):
        return kv_head * group + head, 0, 0

    def whole_kv(kv_head, head, pair, ki_ref, qi_ref):
        return kv_head, 0, 0

    walk = _pair_walk(lk // block_k, q_geometry)
    vmem = _fused_bwd_vmem_bytes(l, lk, d, d_k, d_v, dq_shape.dtype.itemsize,
                                 block_q, block_k)
    return pl.pallas_call(
        functools.partial(_flash_bwd_kernel, scale=scale, shared=shared,
                          geometry=geometry),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh_kv, group, walk[0].shape[0]),
            in_specs=_bwd_in_specs(
                geometry, d, d_k, d_v, q_map, kv_map,
                functools.partial(kv_map, heads=h) if shared else None),
            out_specs=[pl.BlockSpec((1, l, d), whole_q)] + [
                pl.BlockSpec((1,) + s.shape[1:], whole_kv)
                for s in dkv_shape],
            scratch_shapes=[pltpu.VMEM((l, d), jnp.float32),
                            pltpu.VMEM((lk, d), jnp.float32),
                            pltpu.VMEM((lk, d_v), jnp.float32)]),
        out_shape=[dq_shape] + dkv_shape,
        compiler_params=_compiler_params(
            ("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + _VMEM_SLACK),
        interpret=_interpret(),
    )(*walk, *operands)


# What the one-kernel backward may ask of the chip's VMEM: three
# quarters of a v5e's 128 MiB (a kernel that asks for nothing gets
# 16 MiB). A call past it (d = 128 in bfloat16: beyond ~20k) takes the
# two kernels, which hold only blocks. The shapes decide; no option does.
FUSED_BWD_VMEM_BUDGET = 96 * 2 ** 20


def _fused_bwd_vmem_bytes(l: int, lk: int, d: int, d_k: int, d_v: int,
                          itemsize: int, block_q: int, block_k: int) -> int:
    """VMEM the one-kernel backward holds, every width rounded up to
    whole 128-lane tiles as VMEM lays it out: the three whole-sequence
    float32 accumulators, its output blocks (whole sequences too, two
    buffers each), the operand tiles (two buffers each), and the
    [BQ, BK] intermediates (s, p, dp, ds in float32; p and ds again in
    the operand dtype, each with a transposed copy for its product)."""
    d, d_s, d_k, d_v = (-(-n // 128) * 128 for n in (d, d - d_k, d_k, d_v))
    accumulators = 4 * (l * d + lk * d + lk * d_v)
    outputs = 2 * (itemsize * (l * d + lk * d_k + lk * d_v) + 4 * lk * d_s)
    tiles = 2 * (itemsize * (block_q * (d + d_v)
                             + block_k * (d_k + d_s + d_v))
                 + 2 * 4 * block_q * 128)
    scores = (4 * 4 + 4 * itemsize) * block_q * block_k
    return accumulators + outputs + tiles + scores


def _bwd_blocks(l: int, lk: int, d: int, window: Optional[int]) -> tuple:
    """The backward's (block_q, block_k) where the caller names none:
    512, and 1024 where it measured faster (docs/kernels.md): at
    d <= 64 from L 2048 on (every tile halves), and without a window
    from L 8192 on, where the diagonal's whole blocks are a ninth of the
    walk; a window 2,048 wide walks 92 block-units of 512^2 a head at
    1024 where 512 walks 70. Below 2048 a side, 1024 would leave one
    step a head and nothing to pipeline."""
    short = min(l, lk)
    big = short >= 2048 and (d <= 64 or (window is None and short >= 8192))
    return tuple(_auto_block(n, 1024 if big else 512) for n in (l, lk))


def flash_backward_path(l: int, lk: int, d: int, d_k: int, d_v: int,
                        itemsize: int, window: Optional[int] = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None) -> str:
    """``"fused"`` (one kernel; whole-sequence accumulators in VMEM) or
    ``"split"`` (a dQ and a dK/dV kernel that each regenerate the
    scores and hold only blocks). Decided while the step is traced,
    from the shapes alone."""
    auto_q, auto_k = _bwd_blocks(l, lk, d, window)
    need = _fused_bwd_vmem_bytes(l, lk, d, d_k, d_v, itemsize,
                                 block_q or auto_q, block_k or auto_k)
    return "fused" if need <= FUSED_BWD_VMEM_BUDGET else "split"


def _flash_bwd(q, k, v, o, lse, g, causal: bool, scale: float,
               block_q: int, block_k: int, window: Optional[int] = None,
               k_shared=None):
    b, h, l, d = q.shape
    h_kv, lk, d_k = k.shape[1:]
    d_v = v.shape[-1]
    group = h // h_kv
    shared = k_shared is not None
    auto_q, auto_k = _bwd_blocks(l, lk, d, window)
    block_q, block_k = block_q or auto_q, block_k or auto_k
    bh, bh_kv = b * h, b * h_kv
    nq, nk = l // block_q, lk // block_k
    qr = q.reshape(bh, l, d)
    kr = k.reshape(bh_kv, lk, d_k)
    vr = v.reshape(bh_kv, lk, d_v)
    dor = g.reshape(bh, l, d_v)
    # delta_i = rowsum(do_i * o_i): one fused elementwise pass, O(L*D)
    delta = jnp.sum(dor.astype(jnp.float32) *
                    o.reshape(bh, l, d_v).astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (bh, l, 128))
    geometry = dict(block_q=block_q, block_k=block_k, nk=nk,
                    causal=causal, offset=lk - l, window=window)
    # the q-blocks that read a kv-block: the walk of the fused kernel
    # and of the dK/dV kernel
    q_geometry = dict(block_q=block_q, block_k=block_k, nq=nq,
                      causal=causal, offset=lk - l, window=window)
    operands = [qr, kr, vr, dor, lse, delta]
    if shared:
        operands.append(k_shared.reshape(b, lk, d - d_k))
    dkv_shape = [jax.ShapeDtypeStruct((bh_kv, lk, d_k), k.dtype),
                 jax.ShapeDtypeStruct((bh_kv, lk, d_v), v.dtype)]
    if shared:
        # each head's part of the shared head's gradient, in float32
        dkv_shape.append(jax.ShapeDtypeStruct((bh_kv, lk, d - d_k),
                                              jnp.float32))
    dq_shape = jax.ShapeDtypeStruct((bh, l, d), q.dtype)
    path = flash_backward_path(l, lk, d, d_k, d_v, q.dtype.itemsize, window,
                               block_q, block_k)
    dq, dk, dv, *dks = (
        _flash_bwd_fused if path == "fused" else _flash_bwd_split)(
            operands, dq_shape, dkv_shape, scale, group, h, geometry,
            q_geometry)
    grads = (dq.reshape(b, h, l, d), dk.reshape(b, h_kv, lk, d_k),
             dv.reshape(b, h_kv, lk, d_v))
    if not shared:
        return grads + (None,)
    return grads + (dks[0].reshape(b, h, lk, d - d_k).sum(
        1, keepdims=True).astype(k_shared.dtype),)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def pallas_flash_attention_fwd(q, k, v, causal: bool = False,
                               scale: Optional[float] = None,
                               block_q: Optional[int] = None,
                               block_k: Optional[int] = None,
                               window: Optional[int] = None,
                               k_shared=None):
    """Flash attention on q [B, H, L, D], k [B, H_kv, Lk, D],
    v [B, H_kv, Lk, Dv]; exact softmax attention, out [B, H, L, Dv]:
    the values' width need not be the queries' (latent attention reads
    192-wide keys and 128-wide values). ``block_q``/``block_k`` default
    to the largest 128-multiple divisor of each sequence length, capped
    at 1024. ``window`` (causal only) keeps the ``window`` newest keys
    of each row; ``H_kv`` may divide ``H`` (grouped heads).
    ``k_shared`` [B, 1, Lk, Ds]: one more key head whose columns every
    query head's last ``Ds`` columns contract with, beside its own
    ``k`` [B, H, Lk, D - Ds]."""
    out, _ = _flash_fwd(q, k, v, causal, _resolve_scale(scale, q),
                        block_q, block_k, with_lse=False, window=window,
                        k_shared=k_shared)
    return out


def _resolve_scale(scale, q) -> float:
    return scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])


# The forward kernel's two results, named for ``jax.checkpoint``
# policies (``save_only_these_names``): a caller that rematerialises the
# layer round this call keeps them, and its second forward then holds no
# attention kernel (``models/text/sparse_decoder_lm.py``). Outside such
# a policy a name is the identity.
FLASH_OUT_NAME = "flash_attention_out"
FLASH_LSE_NAME = "flash_attention_lse"


def _vjp_fwd(q, k, v, causal, scale, block_q, block_k, window,
             k_shared=None):
    s = _resolve_scale(scale, q)
    out, lse = _flash_fwd(q, k, v, causal, s, block_q, block_k,
                          with_lse=True, window=window, k_shared=k_shared)
    out = checkpoint_name(out, FLASH_OUT_NAME)
    # in the kernel's lanes layout: one value a row would cost a slice
    # here and a broadcast in _flash_bwd for little memory (docs/kernels.md)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return out, (q, k, v, k_shared, out, lse, s)


def _vjp_bwd(causal, scale, block_q, block_k, window, res, g):
    q, k, v, k_shared, out, lse, s = res
    return _flash_bwd(q, k, v, out, lse, g, causal, s, block_q, block_k,
                      window, k_shared)


pallas_flash_attention_fwd.defvjp(_vjp_fwd, _vjp_bwd)


# ------------------------------------------------------------------ #
# EVA: exact keys inside a window, chunk summaries of earlier windows #
# ------------------------------------------------------------------ #
def _eva_geometry(q, k_summary, window: int) -> tuple:
    """(windows, summaries a window) of an EVA call, validated."""
    l, n_sum = q.shape[2], k_summary.shape[2]
    if l % window:
        raise ValueError(f"length {l} is not whole windows of {window}")
    n_win = l // window
    if n_sum % n_win:
        raise ValueError(f"{n_sum} summaries do not divide over {n_win} "
                         "windows")
    return n_win, n_sum // n_win


def _eva_heads(x, n_win: int):
    """[B, H, L, D] with the windows counted as heads,
    [B, H * n_win, L / n_win, D]: a reshape, nothing moves."""
    b, h, l, d = x.shape
    return x.reshape(b, h * n_win, l // n_win, d)


def _eva_rows(x, w: int, window: int):
    """The rows of window ``w`` on the sequence axis of [B, H, L, ...]."""
    return jax.lax.slice_in_dim(x, w * window, (w + 1) * window, axis=2)


def _eva_fwd(q, k, v, k_summary, v_summary, window: int, scale: float):
    """Out [B, H, L, D] and the joint softmax's row logsumexp
    [B*H, L, 128]. The in-window part is the plain causal kernel with
    the windows counted as heads (a reshape, nothing moves); window
    ``w >= 1`` then reads the summaries of windows ``< w`` in a
    rectangular call without a mask, and the two parts join by their
    logsumexps."""
    b, h, l, d = q.shape
    n_win, per = _eva_geometry(q, k_summary, window)
    out, lse = _flash_fwd(*(_eva_heads(x, n_win) for x in (q, k, v)), True,
                          scale, None, None, with_lse=True)
    out = out.reshape(b, h, l, d)
    lse = lse.reshape(b, h, l, 128)
    outs, lses = [_eva_rows(out, 0, window)], [_eva_rows(lse, 0, window)]
    for w in range(1, n_win):
        o_sum, lse_sum = _flash_fwd(
            _eva_rows(q, w, window), k_summary[:, :, :w * per],
            v_summary[:, :, :w * per], False, scale, None, None,
            with_lse=True)
        lse_win = _eva_rows(lse, w, window)
        lse_sum = lse_sum.reshape(b, h, window, 128)
        joint = jnp.logaddexp(lse_win, lse_sum)
        outs.append((
            _eva_rows(out, w, window).astype(jnp.float32)
            * jnp.exp(lse_win - joint)[..., :1]
            + o_sum.astype(jnp.float32)
            * jnp.exp(lse_sum - joint)[..., :1]).astype(out.dtype))
        lses.append(joint)
    return (jnp.concatenate(outs, axis=2),
            jnp.concatenate(lses, axis=2).reshape(b * h, l, 128))


def _eva_bwd(q, k, v, k_summary, v_summary, out, lse, g, window: int,
             scale: float):
    """Gradients of the joint softmax. Every probability of row i,
    token key or summary, is ``exp(s - lse_i)`` under the JOINT
    logsumexp, and ``delta_i = do_i . o_i`` with the joint output: so
    each part's backward is the plain kernel given the joint ``out``
    and ``lse``, and the parts' dq add up. No part keeps its own
    output or logsumexp."""
    b, h, l, d = q.shape
    n_win, per = _eva_geometry(q, k_summary, window)
    qh, kh, vh, oh, gh = (_eva_heads(x, n_win) for x in (q, k, v, out, g))
    dq, dk, dv, _ = _flash_bwd(
        qh, kh, vh, oh, lse.reshape(b * h * n_win, window, 128), gh, True,
        scale, None, None)
    dq = dq.reshape(b, h, l, d)
    dq_parts = [_eva_rows(dq, 0, window)]
    dks = jnp.zeros(k_summary.shape, jnp.float32)
    dvs = jnp.zeros(v_summary.shape, jnp.float32)
    lse = lse.reshape(b, h, l, 128)
    for w in range(1, n_win):
        dq_w, dks_w, dvs_w, _ = _flash_bwd(
            _eva_rows(q, w, window), k_summary[:, :, :w * per],
            v_summary[:, :, :w * per], _eva_rows(out, w, window),
            _eva_rows(lse, w, window).reshape(b * h, window, 128),
            _eva_rows(g, w, window), False, scale, None, None)
        dq_parts.append((_eva_rows(dq, w, window).astype(jnp.float32)
                         + dq_w.astype(jnp.float32)).astype(dq.dtype))
        dks = dks.at[:, :, :w * per].add(dks_w.astype(jnp.float32))
        dvs = dvs.at[:, :, :w * per].add(dvs_w.astype(jnp.float32))
    return (jnp.concatenate(dq_parts, axis=2), dk.reshape(k.shape),
            dv.reshape(v.shape), dks.astype(k_summary.dtype),
            dvs.astype(v_summary.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def pallas_eva_attention(q, k, v, k_summary, v_summary, window: int,
                         scale: Optional[float] = None):
    """EVA attention on q, k, v [B, H, L, D] and the chunk summaries
    k_summary, v_summary [B, H, L / chunk, D]: row i of window
    ``w = i // window`` reads, under ONE softmax, the token keys of its
    own window up to itself and the summaries of every earlier window
    (``m < w * window / chunk``). Out [B, H, L, D]. ``window`` is a
    multiple of 128, and so is the number of summaries a window
    (docs/kernels.md "EVA")."""
    return _eva_fwd(q, k, v, k_summary, v_summary, window,
                    _resolve_scale(scale, q))[0]


def _eva_vjp_fwd(q, k, v, k_summary, v_summary, window, scale):
    s = _resolve_scale(scale, q)
    out, lse = _eva_fwd(q, k, v, k_summary, v_summary, window, s)
    out = checkpoint_name(out, FLASH_OUT_NAME)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return out, (q, k, v, k_summary, v_summary, out, lse, s)


def _eva_vjp_bwd(window, scale, res, g):
    q, k, v, k_summary, v_summary, out, lse, s = res
    return _eva_bwd(q, k, v, k_summary, v_summary, out, lse, g, window, s)


pallas_eva_attention.defvjp(_eva_vjp_fwd, _eva_vjp_bwd)


def eva_pairs(l: int, window: int, per_window: int, d: int = 128) -> dict:
    """Score entries of one head and sequence: ``allowed`` by EVA's
    mask (in-window causal pairs and query x earlier summaries), and
    ``computed`` by the blocks the kernels above walk, forward and
    backward (the in-window walks at their block sizes; the summary
    calls are exact rectangles)."""
    n_win = l // window
    summaries = sum(window * w * per_window for w in range(n_win))
    fq = fk = _auto_block(window)
    bq, bk = _bwd_blocks(window, window, d, None)

    def walked(block_q, block_k):
        return n_win * block_q * block_k * sum(
            hi - lo + 1 for lo, hi in (
                _kv_bounds(i, block_q, block_k, window // block_k, True, 0,
                           None) for i in range(window // block_q)))

    return {"allowed": n_win * window * (window + 1) // 2 + summaries,
            "computed_forward": walked(fq, fk) + summaries,
            "computed_backward": walked(bq, bk) + summaries}
