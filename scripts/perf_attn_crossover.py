#!/usr/bin/env python
"""Attention-kernel crossover: einsum vs owned Pallas flash vs stock
flash, fwd+bwd at BERT-like geometry (h12 d64 bf16), token count held
constant while L sweeps. Interleaved rounds in one process (chip speed
swings ~±25%/hour). Produces the measured table that drives the
``zoo.ops.attention_flash_min_seq`` default (VERDICT r4 item 4).

Usage: python scripts/perf_attn_crossover.py [rounds]
"""
import functools
import json
import sys
import time

import numpy as np

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp

H, D = 12, 64
TOKENS = 48 * 384  # constant work per shape
ITERS = 20


def make_fns(L, causal=False):
    from analytics_zoo_tpu.ops.attention import _einsum_attention
    from analytics_zoo_tpu.ops.pallas_attention import (
        pallas_flash_attention_fwd)
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention)

    b = max(1, TOKENS // L)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, H, L, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, H, L, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, H, L, D), jnp.bfloat16)

    def bench_fn(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32))

        grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        def run():
            return grad(q, k, v)

        def sync(out):
            jax.block_until_ready(out)

        return run, sync

    impls = {
        "einsum": bench_fn(functools.partial(_einsum_attention,
                                             causal=causal)),
        "flash_owned": bench_fn(
            lambda a, b_, c: pallas_flash_attention_fwd(a, b_, c,
                                                        causal)),
        "flash_stock": bench_fn(
            lambda a, b_, c: flash_attention(a, b_, c, causal=causal)),
    }
    return impls, b


def main():
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 5
    table = {}
    for L in (384, 512, 1024, 2048, 4096):
        impls, b = make_fns(L)
        # warm / compile
        for name, (run, sync) in impls.items():
            sync(run())
        times = {n: [] for n in impls}
        for _ in range(rounds):
            for name, (run, sync) in impls.items():
                t0 = time.perf_counter()
                for _i in range(ITERS):
                    out = run()
                sync(out)
                times[name].append((time.perf_counter() - t0) / ITERS)
        row = {n: round(min(ts) * 1e3, 3) for n, ts in times.items()}
        row["batch"] = b
        table[L] = row
        print(f"L={L} b={b}: " + "  ".join(
            f"{n}={v}ms" for n, v in row.items() if n != "batch"),
            flush=True)
    print(json.dumps(table))


if __name__ == "__main__":
    main()
