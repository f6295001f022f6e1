#!/usr/bin/env python
"""Full BERT train-step timing per attention impl / batch (big
dispatches only)."""
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
    leaf = jax.tree_util.tree_leaves(r)[0]
    val = leaf if getattr(leaf, "ndim", 0) == 0 else jnp.sum(leaf)
    float(jax.device_get(val))


def bert_step(batch, impl, seq=384, label=""):
    from analytics_zoo_tpu.common.config import get_config
    from analytics_zoo_tpu.models.text.bert_squad import (
        BERTForSQuAD, squad_span_loss)

    get_config().set("zoo.ops.attention_impl", impl)
    mod = BERTForSQuAD(vocab=30522, dtype=jnp.bfloat16)
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
    print(f"BERT {impl}{label} b{batch}: {dt*1e3:.1f} ms/step, "
          f"{1/dt:.2f} steps/s, MFU {mfu:.3f} (compile {compile_s:.0f}s)",
          flush=True)
    return dt, mfu


if __name__ == "__main__":
    import sys as _sys

    print(jax.devices(), flush=True)
    configs = _sys.argv[1:] or ["einsum:32", "einsum:64", "flash:64",
                                "einsum:128"]
    for c in configs:
        impl, b = c.split(":")
        try:
            bert_step(int(b), impl)
        except Exception as e:
            print(f"{c} FAILED: {type(e).__name__}: {e}", flush=True)
