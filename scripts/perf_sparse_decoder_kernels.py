"""On-chip timings behind two choices of the sparse decoder
(docs/kernels.md): the grouped expert products (``jax.lax.ragged_dot``
against ``megablox.gmm`` at several tilings) and the attention kernels
(this repo's flash kernels with a window and grouped KV heads against
JAX's ``splash_attention``), forward + backward at the published
widths. Run through the chip tool; prints one JSON line per reading.

    python scripts/perf_sparse_decoder_kernels.py [--skip-splash] [--skip-grouped]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

D, WIDTH, EXPERTS, L, HEADS, KV_HEADS, HEAD_DIM, WINDOW = (
    2048, 1024, 16, 8192, 32, 4, 128, 2048)


def timed(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def report(**kv):
    print(json.dumps(kv), flush=True)


def grouped(rows: int, held: int):
    """SwiGLU over ``held`` sorted rows in a buffer of ``rows``."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (rows, D), jnp.bfloat16)
    w1 = jax.random.normal(ks[1], (EXPERTS, D, WIDTH), jnp.bfloat16) * 0.02
    w3 = jax.random.normal(ks[2], (EXPERTS, D, WIDTH), jnp.bfloat16) * 0.02
    w2 = jax.random.normal(ks[3], (EXPERTS, WIDTH, D), jnp.bfloat16) * 0.02
    sizes = jnp.full((EXPERTS,), held // EXPERTS, jnp.int32)
    mask = (jnp.arange(rows) < held)[:, None]

    def swiglu(dot):
        def f(x, w1, w3, w2):
            h = jax.nn.silu(dot(x, w1)) * dot(x, w3)
            return jnp.sum(jnp.where(mask, dot(h, w2), 0).astype(
                jnp.float32))
        return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3)))

    flops = 3 * 3 * 2 * held * D * WIDTH
    impls = {"ragged_dot": lambda a, b: jax.lax.ragged_dot(a, b, sizes)}
    for tiling in ((512, 1024, 1024), (512, 512, 1024), (256, 1024, 1024),
                   (512, 2048, 1024), (1024, 1024, 1024)):
        impls["gmm" + str(tiling)] = (
            lambda a, b, t=tiling: gmm(a, b, sizes, a.dtype, t, None, None,
                                       False, False))
    for name, dot in impls.items():
        try:
            ms = timed(swiglu(dot), x, w1, w3, w2)
            report(what="swiglu_fwd_bwd", impl=name, rows=rows, held=held,
                   ms=ms, tflops=flops / ms / 1e9)
        except Exception as e:  # a tiling the compiler refuses
            report(what="swiglu_fwd_bwd", impl=name, rows=rows, held=held,
                   error=str(e)[:200])


def attention(skip_splash: bool):
    from analytics_zoo_tpu.ops.attention import dot_product_attention
    from analytics_zoo_tpu.ops.pallas_attention import (
        pallas_flash_attention_fwd)

    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, HEADS, L, HEAD_DIM), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, KV_HEADS, L, HEAD_DIM), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, KV_HEADS, L, HEAD_DIM), jnp.bfloat16)
    for window in (WINDOW, None):
        pairs = (L * (L + 1) // 2 if window is None
                 else window * (window + 1) // 2 + (L - window) * window)
        flops = 3 * pairs * 4 * HEAD_DIM * HEADS

        def owned(q, k, v):
            return jnp.sum(dot_product_attention(
                q, k, v, causal=True, window=window).astype(jnp.float32))

        ms = timed(jax.jit(jax.grad(owned, argnums=(0, 1, 2))), q, k, v)
        report(what="attention_fwd_bwd", impl="owned_flash", window=window,
               ms=ms, model_tflops=flops / ms / 1e9)
        ms = timed(jax.jit(owned), q, k, v)
        report(what="attention_fwd", impl="owned_flash", window=window,
               ms=ms, model_tflops=flops / 3 / ms / 1e9)
        for blocks in ((512, 512), (512, 1024), (1024, 512)):
            def blocked(q, k, v):
                return jnp.sum(pallas_flash_attention_fwd(
                    q, k, v, True, None, *blocks, window).astype(
                        jnp.float32))

            try:
                ms = timed(jax.jit(blocked), q, k, v)
                report(what="attention_fwd", impl=f"owned_flash{blocks}",
                       window=window, ms=ms,
                       model_tflops=flops / 3 / ms / 1e9)
            except Exception as e:
                report(what="attention_fwd", impl=f"owned_flash{blocks}",
                       window=window, error=str(e)[:200])
        if skip_splash:
            continue
        try:
            from jax.experimental.pallas.ops.tpu.splash_attention import (
                splash_attention_kernel as sk, splash_attention_mask as sm)

            one = (sm.CausalMask((L, L)) if window is None
                   else sm.LocalMask((L, L), (window - 1, 0), 0))
            group = HEADS // KV_HEADS
            kernel = sk.make_splash_mqa_single_device(
                sm.MultiHeadMask([one] * group))
            scale = 1.0 / np.sqrt(HEAD_DIM)

            def splash(q, k, v):
                qg = (q[0] * scale).astype(q.dtype).reshape(
                    KV_HEADS, group, L, HEAD_DIM)
                return jnp.sum(jax.vmap(kernel)(qg, k[0], v[0]).astype(
                    jnp.float32))

            ms = timed(jax.jit(jax.grad(splash, argnums=(0, 1, 2))), q, k, v)
            report(what="attention_fwd_bwd", impl="splash_mqa",
                   window=window, ms=ms, model_tflops=flops / ms / 1e9)
        except Exception as e:
            report(what="attention_fwd_bwd", impl="splash_mqa",
                   window=window, error=str(e)[:300])


if __name__ == "__main__":
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("perf_sparse_decoder_kernels: needs a TPU")
    if "--skip-grouped" not in sys.argv:
        grouped(rows=8192, held=8192)
        grouped(rows=65536, held=8192)
    attention("--skip-splash" in sys.argv)
