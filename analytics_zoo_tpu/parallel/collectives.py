"""Collective primitives for use inside ``shard_map``-ed functions.

One set of XLA collectives replaces the reference's five transport stacks
(Spark BlockManager shuffle+broadcast, TF RING collectives, Gloo, Horovod,
MXNet PS-Lite -- SURVEY.md section 2.3). The semantics of BigDL's
``AllReduceParameter`` (reduce-scatter then re-fetch == allreduce,
ref: docs/docs/wp-bigdl.md:138-160) are exactly ``psum``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax


def all_reduce_sum(x: Any, axis_name: str) -> Any:
    return jax.tree_util.tree_map(lambda t: lax.psum(t, axis_name), x)


def all_reduce_mean(x: Any, axis_name: str) -> Any:
    return jax.tree_util.tree_map(lambda t: lax.pmean(t, axis_name), x)


def all_gather(x: Any, axis_name: str, axis: int = 0, tiled: bool = True) -> Any:
    return jax.tree_util.tree_map(
        lambda t: lax.all_gather(t, axis_name, axis=axis, tiled=tiled), x)


def reduce_scatter(x: Any, axis_name: str, axis: int = 0) -> Any:
    return jax.tree_util.tree_map(
        lambda t: lax.psum_scatter(t, axis_name, scatter_dimension=axis,
                                   tiled=True), x)


def ring_permute(x: Any, axis_name: str, shift: int = 1) -> Any:
    """Send to the next device on the ring (rank -> rank+shift mod N)."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.tree_util.tree_map(
        lambda t: lax.ppermute(t, axis_name, perm), x)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str) -> int:
    """Static size of a named mesh axis from inside a mapped body."""
    return lax.axis_size(axis_name)


def _q8(t: jnp.ndarray):
    """Symmetric per-shard int8 quantization: (int8 payload, f32 scale).
    The scale floor keeps all-zero shards finite (0/eps = 0, exact)."""
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(t / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def quantized_psum(x: Any, axis_name: str) -> Any:
    """Approximate ``psum`` that moves int8 instead of f32/bf16 across
    the interconnect (the EQuARX idiom, arXiv:2506.17615: quantized
    AllReduce built for exactly the TPU tensor-parallel serving regime).

    Each shard quantizes its operand symmetrically to int8 with one
    per-shard scale, all-gathers the int8 payloads (+ the tiny scale
    vector), then dequantizes and reduces locally in the operand dtype
    -- so the cross-chip bytes are ~1/4 of an f32 ring allreduce (1/2
    of bf16) at the cost of a bounded relative error (~1/127 per
    shard's contribution). Exact ``all_reduce_sum`` stays the default
    everywhere; this is the opt-in wire-compression path
    (``zoo.serving.shard.quantized_collectives``)."""
    def one(t):
        q, scale = _q8(t)
        qs = lax.all_gather(q, axis_name, axis=0, tiled=False)
        ss = lax.all_gather(scale, axis_name, axis=0, tiled=False)
        deq = qs.astype(t.dtype) * ss.reshape(
            (-1,) + (1,) * t.ndim).astype(t.dtype)
        return jnp.sum(deq, axis=0)

    return jax.tree_util.tree_map(one, x)


def quantized_all_gather(x: Any, axis_name: str, axis: int = 0) -> Any:
    """Approximate tiled ``all_gather`` moving int8 payloads + per-shard
    scales instead of full-precision shards (the same EQuARX wire
    compression applied to a gather: ~1/4 the cross-chip bytes of f32).
    Shards concatenate along ``axis`` in shard order, exactly like
    ``lax.all_gather(..., tiled=True)``; each shard's slice carries its
    own rescale. The sharded serving layer uses this to re-assemble
    tensor-parallel parameter shards per dispatch
    (:mod:`analytics_zoo_tpu.inference.sharded`)."""
    def one(t):
        q, scale = _q8(t)
        qs = lax.all_gather(q, axis_name, axis=0, tiled=False)
        ss = lax.all_gather(scale, axis_name, axis=0, tiled=False)
        deq = qs.astype(t.dtype) * ss.reshape(
            (-1,) + (1,) * t.ndim).astype(t.dtype)
        # [N, ...local...] -> concatenation along `axis`, shard-major
        # (the NamedSharding slice order)
        out = jnp.moveaxis(deq, 0, axis)
        shape = (t.shape[:axis] + (t.shape[axis] * deq.shape[0],)
                 + t.shape[axis + 1:])
        return out.reshape(shape)

    return jax.tree_util.tree_map(one, x)


def global_norm(tree: Any, axis_name: str = None) -> jnp.ndarray:
    """L2 norm over an entire pytree (used for global gradient clipping,
    matching the reference's global-gradient L2 clipping semantics,
    ref: pyzoo/zoo/tfpark/tf_optimizer.py:392-396).

    When the tree's leaves are *sharded* across a mesh axis inside a
    ``shard_map`` body (e.g. FSDP), pass ``axis_name`` so the squared sum
    is psum-reduced to the true global norm instead of a per-shard norm.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    sq = sum(jnp.sum(jnp.square(l)) for l in leaves)
    if axis_name is not None:
        sq = lax.psum(sq, axis_name)
    return jnp.sqrt(sq)
