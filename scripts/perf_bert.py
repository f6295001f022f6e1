#!/usr/bin/env python
"""BERT perf exploration on the real chip: step time vs batch, attention
share, matmul roofline. Prints JSON lines; run on TPU."""
import functools
import sys
import time

import numpy as np

sys.path.insert(0, "/root/repo")
import jax
import jax.numpy as jnp
import optax

PEAK = 197e12


def sync(r):
    jax.block_until_ready(r)


def timeit(fn, *args, iters=10, warmup=2):
    for _ in range(warmup):
        r = fn(*args)
    sync(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
    sync(r)
    return (time.perf_counter() - t0) / iters


def roofline():
    # big matmul chain to sanity-check achievable peak
    a = jnp.ones((8192, 8192), jnp.bfloat16)
    b = jnp.ones((8192, 8192), jnp.bfloat16)

    @jax.jit
    def mm(a, b):
        return a @ b

    dt = timeit(mm, a, b, iters=20)
    fl = 2 * 8192**3
    print(f"ROOFLINE matmul 8192^3: {dt*1e3:.2f} ms, "
          f"{fl/dt/1e12:.1f} TF/s ({fl/dt/PEAK:.2f} of peak)", flush=True)


def attention_share(batch=32, seq=384):
    from analytics_zoo_tpu.ops.attention import dot_product_attention
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (batch, 12, seq, 64), jnp.bfloat16)

    def attn_loss(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v).astype(jnp.float32))

    g = jax.jit(jax.grad(attn_loss, argnums=(0, 1, 2)))
    dt = timeit(lambda: g(q, q, q), iters=20)
    # fwd+bwd attention flops: ~ 4*2*B*H*L^2*D*... fwd=4*B*H*L*L*D ; bwd ~2.5x
    fl = 3.5 * 4 * batch * 12 * seq * seq * 64
    print(f"ATTN b{batch} l{seq}: {dt*1e3:.3f} ms/step x12layers="
          f"{dt*12*1e3:.1f} ms, {fl/dt/1e12:.1f} TF/s", flush=True)


def bert_step(batch, seq=384, dtype=jnp.bfloat16, remat=None, label=""):
    from analytics_zoo_tpu.models.text.bert_squad import (
        BERTForSQuAD, squad_span_loss)
    mod = BERTForSQuAD(vocab=30522, dtype=dtype)
    x = {"input_ids": np.random.RandomState(0).randint(
        0, 30522, (batch, seq)).astype(np.int32)}
    y = np.stack([np.random.randint(0, seq, batch),
                  np.random.randint(0, seq, batch)], 1).astype(np.int32)
    variables = mod.init(jax.random.PRNGKey(0),
                         {"input_ids": x["input_ids"][:1]}, train=False)
    tx = optax.adam(1e-4)
    params = variables["params"]
    opt_state = tx.init(params)

    def loss_fn(p, x, y, rng):
        preds = mod.apply({"params": p}, x, train=True,
                          rngs={"dropout": rng})
        return squad_span_loss(preds, y)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, x, y, rng):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y, rng)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    rng = jax.random.PRNGKey(1)
    # donated buffers: re-feed outputs
    t0 = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, x, y, rng)
    sync(loss)
    compile_s = time.perf_counter() - t0
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, x, y, rng)
    sync(loss)
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step(params, opt_state, x, y, rng)
    sync(loss)
    dt = (time.perf_counter() - t0) / iters
    p_dense = sum(int(l.size) for p, l in
                  jax.tree_util.tree_flatten_with_path(params)[0]
                  if "embed" not in "/".join(str(s) for s in p).lower())
    fpt = 6 * p_dense + 12 * 12 * 768 * seq
    mfu = batch * seq * fpt / dt / PEAK
    print(f"BERT{label} b{batch}: {dt*1e3:.1f} ms/step, "
          f"{1/dt:.2f} steps/s, MFU {mfu:.3f} (compile {compile_s:.0f}s)",
          flush=True)
    return dt, mfu


if __name__ == "__main__":
    print(jax.devices(), flush=True)
    roofline()
    attention_share(32)
    attention_share(64)
    for b in (32, 64, 128):
        try:
            bert_step(b)
        except Exception as e:
            print(f"BERT b{b} FAILED: {type(e).__name__}: {e}",
                  flush=True)
