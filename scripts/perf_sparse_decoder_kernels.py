"""On-chip timings behind two choices of the sparse decoder
(docs/kernels.md): the grouped expert products (``jax.lax.ragged_dot``
against ``megablox.gmm`` at several tilings) and the attention kernels
(this repo's flash kernels with a window and grouped KV heads against
JAX's ``splash_attention``), forward + backward at the published
widths. ``--latent`` times instead the latent-attention decoder's two:
the flash kernels at [1, 16, 8192, 192 | 128] with the rotary key read
as one shared head against the keys joined in HBM before the call, and
the grouped products at 1,408-wide experts under several tilings.
``--backward`` times the flash backward alone (``_flash_bwd`` on a kept
forward's output and logsumexp) at the three published shapes under
several block choices, beside forward + backward through the
dispatcher, and says how far its gradients lie from the two-kernel
path's; ``--eva`` times the byte decoder's EVA attention at
[1, 32, 8192, 128] with windows of 2,048 and chunks of 16: the joint
call, its in-window part alone, the rectangular calls over the chunk
summaries alone, and the pooling; ``--repo <checkout>`` imports the package from another checkout
(the parent commit unpacked), for the other side of the table. Run
through the chip tool; prints one JSON line per reading.

    python scripts/perf_sparse_decoder_kernels.py [--skip-splash] [--skip-grouped]
    python scripts/perf_sparse_decoder_kernels.py --latent
    python scripts/perf_sparse_decoder_kernels.py --backward [--repo <checkout>]
    python scripts/perf_sparse_decoder_kernels.py --eva
    JAX_PLATFORMS=cpu python scripts/perf_sparse_decoder_kernels.py --rehearse
"""

import json
import os
import sys
import time

REPO = (os.path.abspath(sys.argv[sys.argv.index("--repo") + 1])
        if "--repo" in sys.argv
        else os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

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


def latent_attention():
    """Forward + backward from (q, k_nope, k_rot, v) to all four
    gradients, so that the join, the repeat and the sum over heads of
    the form that builds 192-wide keys in HBM are inside the time."""
    from analytics_zoo_tpu.ops.attention import dot_product_attention

    heads, nope, rot, v_dim = 16, 128, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (1, heads, L, nope + rot), jnp.bfloat16)
    k_nope = jax.random.normal(ks[1], (1, heads, L, nope), jnp.bfloat16)
    k_rot = jax.random.normal(ks[2], (1, 1, L, rot), jnp.bfloat16)
    v = jax.random.normal(ks[3], (1, heads, L, v_dim), jnp.bfloat16)
    flops = 3 * (L * (L + 1) // 2) * 2 * (nope + rot + v_dim) * heads

    def shared(q, k_nope, k_rot, v):
        return dot_product_attention(q, k_nope, v, causal=True,
                                     k_shared=k_rot)

    def joined(q, k_nope, k_rot, v):
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rot, (1, heads, L, rot))], axis=-1)
        return dot_product_attention(q, k, v, causal=True)

    def padded(q, k_nope, k_rot, v):
        """What a one-width kernel would need: V padded to 192."""
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rot, (1, heads, L, rot))], axis=-1)
        wide = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, nope + rot - v_dim)))
        return dot_product_attention(q, k, wide, causal=True)[..., :v_dim]

    outs = {}
    for name, fn in (("shared_rotary_head", shared),
                     ("keys_joined_in_hbm", joined),
                     ("values_padded_to_192", padded)):
        def total(*a, fn=fn):
            return jnp.sum(fn(*a).astype(jnp.float32))

        outs[name] = jax.jit(fn)(q, k_nope, k_rot, v)
        ms = timed(jax.jit(jax.grad(total, argnums=(0, 1, 2, 3))),
                   q, k_nope, k_rot, v)
        report(what="latent_attention_fwd_bwd", impl=name, ms=ms,
               model_tflops=flops / ms / 1e9)
        ms = timed(jax.jit(fn), q, k_nope, k_rot, v)
        report(what="latent_attention_fwd", impl=name, ms=ms,
               model_tflops=flops / 3 / ms / 1e9)
    base = outs["keys_joined_in_hbm"].astype(jnp.float32)
    for name, out in outs.items():
        report(what="latent_attention_agreement", impl=name,
               max_abs_diff_from_joined=float(jnp.max(jnp.abs(
                   out.astype(jnp.float32) - base))))


def backward(small: bool = False):
    """The flash backward alone at the three published shapes (the
    latent layer, the full and the window GQA layer), by block choice."""
    from analytics_zoo_tpu.ops import pallas_attention as pa
    from analytics_zoo_tpu.ops.attention import dot_product_attention

    l = 1024 if small else L
    window = 256 if small else WINDOW
    causal_pairs = l * (l + 1) // 2
    shapes = {  # name: (heads, kv heads, d, shared columns, d_v, window)
        "latent": (16, 16, 192, 64, 128, None),
        "full_gqa": (HEADS, KV_HEADS, HEAD_DIM, 0, HEAD_DIM, None),
        "window_gqa": (HEADS, KV_HEADS, HEAD_DIM, 0, HEAD_DIM, window),
    }
    for name, (h, h_kv, d, d_s, d_v, win) in shapes.items():
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        q = jax.random.normal(ks[0], (1, h, l, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, h_kv, l, d - d_s), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, h_kv, l, d_v), jnp.bfloat16)
        k_s = (jax.random.normal(ks[3], (1, 1, l, d_s), jnp.bfloat16)
               if d_s else None)
        g = jax.random.normal(ks[4], (1, h, l, d_v), jnp.bfloat16)
        pairs = (causal_pairs if win is None
                 else win * (win + 1) // 2 + (l - win) * win)
        flops = pairs * 2 * (d + d_v) * h      # one pass; backward is two
        scale = 1.0 / np.sqrt(d)
        out, lse = jax.jit(lambda q, k, v, k_s: pa._flash_fwd(
            q, k, v, True, scale, None, None, with_lse=True, window=win,
            k_shared=k_s))(q, k, v, k_s)

        def bwd(blocks):
            return jax.jit(lambda q, k, v, out, lse, g, k_s: pa._flash_bwd(
                q, k, v, out, lse, g, True, scale, *blocks, win, k_s))

        def total(q, k, v, k_s):
            return jnp.sum(dot_product_attention(
                q, k, v, causal=True, window=win, k_shared=k_s).astype(
                    jnp.float32))

        ms = timed(jax.jit(jax.grad(total, argnums=(0, 1, 2))), q, k, v, k_s,
                   reps=10)
        report(what="flash_fwd_bwd", shape=name, ms=ms,
               model_tflops=3 * flops / ms / 1e9)
        grads = {}
        for blocks in ((None, None), (512, 512), (512, 1024), (1024, 512),
                       (1024, 1024), (256, 512), (256, 1024)):
            try:
                fn = bwd(blocks)
                grads[blocks] = fn(q, k, v, out, lse, g, k_s)
                ms = timed(fn, q, k, v, out, lse, g, k_s, reps=10)
                report(what="flash_bwd", shape=name, blocks=blocks, ms=ms,
                       model_tflops=2 * flops / ms / 1e9)
            except Exception as e:  # blocks the compiler refuses
                report(what="flash_bwd", shape=name, blocks=blocks,
                       error=str(e)[:200])
        if not hasattr(pa, "FUSED_BWD_VMEM_BUDGET"):
            continue
        budget, pa.FUSED_BWD_VMEM_BUDGET = pa.FUSED_BWD_VMEM_BUDGET, 0
        try:
            fn = bwd((None, None))
            split = fn(q, k, v, out, lse, g, k_s)
            ms = timed(fn, q, k, v, out, lse, g, k_s, reps=10)
        finally:
            pa.FUSED_BWD_VMEM_BUDGET = budget
        report(what="flash_bwd", shape=name, blocks="two kernels", ms=ms,
               model_tflops=2 * flops / ms / 1e9)
        for blocks, got in grads.items():
            worst = max(
                float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32)))
                      / jnp.max(jnp.abs(b.astype(jnp.float32))))
                for a, b in zip(got, split) if a is not None)
            report(what="flash_bwd_agreement", shape=name, blocks=blocks,
                   worst_rel_diff_from_two_kernels=worst)


def latent_grouped(rows: int = 49152, held: int = 6144):
    """SwiGLU over 8 experts [2048 -> 1408 -> 2048] with ``w1 | w3`` side
    by side, as ``DroplessExperts`` runs it: ``grouped_dot`` (each pass
    tiled for its own shapes) against ``megablox``' own VJP under one
    tile, which at 1,408 = 11 x 128 does not divide: what the library
    does then is in the error column and the time."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from analytics_zoo_tpu.keras.layers.moe import grouped_dot

    d, width, experts = 2048, 1408, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (rows, d), jnp.bfloat16)
    w13 = jax.random.normal(ks[1], (experts, d, 2 * width),
                            jnp.bfloat16) * 0.02
    w2 = jax.random.normal(ks[2], (experts, width, d), jnp.bfloat16) * 0.02
    sizes = jnp.full((experts,), held // experts, jnp.int32)
    mask = (jnp.arange(rows) < held)[:, None]

    def swiglu(dot):
        def f(x, w13, w2):
            ab = dot(x, w13)
            h = jax.nn.silu(ab[:, :width]) * ab[:, width:]
            return jnp.sum(jnp.where(mask, dot(h.astype(x.dtype), w2),
                                     0).astype(jnp.float32))
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))

    flops = 3 * 3 * 2 * held * d * width
    impls = {"ragged_dot": lambda a, b: jax.lax.ragged_dot(a, b, sizes),
             "grouped_dot_per_pass": lambda a, b: grouped_dot(a, b, sizes)}
    for tiling in ((512, 1024, 1024), (512, 512, 512), (512, 256, 256),
                   (512, 128, 128), (512, 1408, 1408)):
        impls["gmm" + str(tiling)] = (
            lambda a, b, t=tiling: gmm(a, b, sizes, a.dtype, t, None, None,
                                       False, False))
    want = None
    for name, dot in impls.items():
        try:
            fn = swiglu(dot)
            value, grads = fn(x, w13, w2)
            got = [np.asarray(value, np.float32)] + [
                np.asarray(g[:held] if g.shape[0] == rows else g,
                           np.float32) for g in grads]
            want = want or got
            worst = max(float(np.max(np.abs(g - w)) / (np.max(np.abs(w))
                                                       + 1e-30))
                        for g, w in zip(got, want))
            ms = timed(fn, x, w13, w2)
            report(what="latent_swiglu_fwd_bwd", impl=name, rows=rows,
                   held=held, ms=ms, tflops=flops / ms / 1e9,
                   worst_rel_diff_from_ragged_dot=worst)
        except Exception as e:  # a tiling the compiler refuses
            report(what="latent_swiglu_fwd_bwd", impl=name, rows=rows,
                   held=held, error=str(e)[:200])


def eva(small: bool = False):
    """EVA attention forward and forward + backward, whole and by part:
    what the joint call costs over its in-window kernel (the calls over
    the summaries, the join by logsumexp, the slices), and the pooling
    that feeds it."""
    from analytics_zoo_tpu.keras.layers.byte_decoder import chunk_summaries
    from analytics_zoo_tpu.ops import pallas_attention as pa

    heads, length, window, chunk, d = ((2, 512, 256, 2, 64) if small else
                                       (HEADS, L, WINDOW, 16, HEAD_DIM))
    dtype = jnp.float32 if small else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (jax.random.normal(key, (1, heads, length, d), dtype)
               for key in ks[:3])
    phi, mu = (jax.random.normal(key, (heads, d)) * d ** -0.5
               for key in ks[3:])
    n_win, per = length // window, window // chunk

    def pool(k, v, phi, mu):
        k_sum, v_sum = chunk_summaries(k, v, phi, mu, chunk, d ** -0.5)
        return k_sum.astype(dtype), v_sum.astype(dtype)

    k_sum, v_sum = jax.jit(pool)(k, v, phi, mu)

    def joint(q, k, v, k_sum, v_sum):
        return pa.pallas_eva_attention(q, k, v, k_sum, v_sum, window, None)

    def in_window(q, k, v):
        fold = (1, heads * n_win, window, d)
        return pa.pallas_flash_attention_fwd(
            q.reshape(fold), k.reshape(fold), v.reshape(fold), True)

    def summaries(q, k_sum, v_sum):
        return [pa.pallas_flash_attention_fwd(
            q[:, :, w * window:(w + 1) * window], k_sum[:, :, :w * per],
            v_sum[:, :, :w * per], False) for w in range(1, n_win)]

    def total(fn):
        return lambda *a: sum(
            jnp.sum(o.astype(jnp.float32))
            for o in jax.tree_util.tree_leaves(fn(*a)))

    for name, fn, args in (
            ("eva_joint", joint, (q, k, v, k_sum, v_sum)),
            ("eva_in_window_part", in_window, (q, k, v)),
            ("eva_summary_part", summaries, (q, k_sum, v_sum)),
            ("eva_chunk_summaries", pool, (k, v, phi, mu))):
        fwd = timed(jax.jit(fn), *args)
        both = timed(jax.jit(jax.grad(
            total(fn), argnums=tuple(range(len(args))))), *args)
        report(what=name, shape=[1, heads, length, d], window=window,
               chunk=chunk, fwd_ms=fwd, fwd_bwd_ms=both)


if __name__ == "__main__":
    if "--rehearse" in sys.argv:   # the CPU, a tiny size: checks the script
        backward(small=True)
        eva(small=True)
        raise SystemExit(0)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("perf_sparse_decoder_kernels: needs a TPU")
    if "--backward" in sys.argv:
        backward()
        raise SystemExit(0)
    if "--eva" in sys.argv:
        eva()
        raise SystemExit(0)
    if "--latent" in sys.argv:
        latent_attention()
        latent_grouped()
        raise SystemExit(0)
    if "--skip-grouped" not in sys.argv:
        grouped(rows=8192, held=8192)
        grouped(rows=65536, held=8192)
    attention("--skip-splash" in sys.argv)
