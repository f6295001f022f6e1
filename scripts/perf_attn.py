#!/usr/bin/env python
"""Attention implementation shootout at BERT-base shapes on real TPU.
Chained inside lax.fori_loop so per-dispatch overhead amortizes."""
import sys
import time

import numpy as np

sys.path.insert(0, "/root/repo")
import jax
import jax.numpy as jnp

PEAK = 197e12


def sync(r):
    leaf = jax.tree_util.tree_leaves(r)[0]
    val = leaf if getattr(leaf, "ndim", 0) == 0 else jnp.sum(leaf)
    float(jax.device_get(val))


def chain_bench(name, attn_fn, b, h, l, d, iters=20):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, l, d),
                          jnp.bfloat16)

    @jax.jit
    def run(q):
        def body(i, q):
            def loss(q):
                return jnp.sum(attn_fn(q, q, q).astype(jnp.float32))

            g = jax.grad(loss)(q)
            return q + 0.0001 * g.astype(q.dtype)

        return jax.lax.fori_loop(0, iters, body, q)

    t0 = time.perf_counter()
    sync(run(q))
    comp = time.perf_counter() - t0
    t0 = time.perf_counter()
    sync(run(q))
    dt = (time.perf_counter() - t0) / iters
    # fwd 4*b*h*l*l*d MACs*2? use flops = 2 matmuls: 2*2*b*h*l*l*d fwd,
    # bwd ~2.5x -> 3.5x total
    fl = 3.5 * 4 * b * h * l * l * d
    print(f"{name} b{b} l{l} d{d}: {dt*1e3:.2f} ms fwd+bwd, "
          f"{fl/dt/1e12:.1f} TF/s (compile {comp:.0f}s)", flush=True)
    return dt


def stock_flash(q, k, v):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention)

    return flash_attention(q, k, v, causal=False,
                           sm_scale=1.0 / np.sqrt(q.shape[-1]))


def jnp_ref(q, k, v):
    from analytics_zoo_tpu.ops.attention import reference_attention

    return reference_attention(q, k, v)


def xla_dpa(q, k, v):
    # jax.nn.dot_product_attention expects [B, L, H, D]
    qt = q.transpose(0, 2, 1, 3)
    out = jax.nn.dot_product_attention(qt, k.transpose(0, 2, 1, 3),
                                       v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)


def own_padded(q, k, v):
    from analytics_zoo_tpu.ops.pallas_attention import (
        pallas_flash_attention_fwd)

    d = q.shape[-1]
    pad = [(0, 0)] * 3 + [(0, 128 - d)]
    qp, kp, vp = (jnp.pad(t, pad) for t in (q, k, v))
    out = pallas_flash_attention_fwd(qp, kp, vp, False,
                                     1.0 / np.sqrt(d))
    return out[..., :d]


def stock_flash_bq(bq, bk):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes, flash_attention)

    def fn(q, k, v):
        l = q.shape[2]
        bs = BlockSizes(
            block_q=min(bq, l), block_k_major=min(bk, l),
            block_k=min(bk, l), block_b=1,
            block_q_major_dkv=min(bq, l), block_k_major_dkv=min(bk, l),
            block_k_dkv=min(bk, l), block_q_dkv=min(bq, l),
            block_k_major_dq=min(bk, l), block_k_dq=min(bk, l),
            block_q_dq=min(bq, l))
        return flash_attention(q, k, v, causal=False,
                               sm_scale=1.0 / np.sqrt(q.shape[-1]),
                               block_sizes=bs)

    return fn


if __name__ == "__main__":
    print(jax.devices(), flush=True)
    shapes = [(32, 12, 384, 64)]
    for b, h, l, d in shapes:
        chain_bench("jnp_einsum", jnp_ref, b, h, l, d)
        chain_bench("xla_dpa", xla_dpa, b, h, l, d)
        chain_bench("stock_flash_default", stock_flash, b, h, l, d)
        chain_bench("stock_flash_128/128", stock_flash_bq(128, 128),
                    b, h, l, d)
        chain_bench("own_kernel_padded128", own_padded, b, h, l, d)
    chain_bench("jnp_einsum", jnp_ref, 64, 12, 384, 64)
    chain_bench("xla_dpa", xla_dpa, 64, 12, 384, 64)
