#!/usr/bin/env python
"""Gradient-accumulation A/B for BERT SQuAD fine-tune (r5 target:
bert_mfu >= 0.40 recorded).

One process, interleaved round-robin windows over configs -- the chip's
speed swings ~±25%/hour, so only windows measured side by side compare.
Each window runs the SAME token count (48*16*384) through the full
Estimator.fit loop.

Usage: python scripts/perf_bert_accum.py [rounds]
"""
import json
import sys
import time

import numpy as np

sys.path.insert(0, "/root/repo")

BERT_VOCAB, SEQ = 30522, 384
TOKENS = 48 * 16  # samples per window (x SEQ tokens)
PEAK = 197e12


def build(batch, accum):
    from analytics_zoo_tpu.common.config import get_config
    from analytics_zoo_tpu.models.text.bert_squad import BERTSQuAD

    get_config().set("zoo.train.log_every_n_steps", 100000)
    rng = np.random.RandomState(0)
    n = TOKENS
    x = {"input_ids": rng.randint(0, BERT_VOCAB, (n, SEQ)
                                  ).astype(np.int32)}
    y = np.stack([rng.randint(0, SEQ, n), rng.randint(0, SEQ, n)],
                 axis=1).astype(np.int32)
    model = BERTSQuAD(vocab=BERT_VOCAB, dtype="bfloat16")
    if accum > 1:
        model.compile(grad_accum_steps=accum)
    model.fit((x, y), batch_size=batch, epochs=1)  # compile epoch
    return model, x, y


def window(model, x, y, batch):
    est = model.estimator
    t0 = time.perf_counter()
    model.fit((x, y), batch_size=batch, epochs=est.epoch + 1)
    dt = time.perf_counter() - t0
    return TOKENS * SEQ / dt  # tokens/sec


def main():
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 6
    import jax  # noqa: F401  (device init before timing)

    from analytics_zoo_tpu.models.text.bert_squad import BERTSQuAD

    cfgs = [("b48", 48, 1), ("b96a2", 96, 2), ("b192a4", 192, 4)]
    models = {}
    for name, batch, accum in cfgs:
        print(f"building {name} ...", flush=True)
        models[name] = build(batch, accum)
    # flops/token: same formula as bench.py measure_bert
    m0 = models["b48"][0]
    import jax as _j

    p_dense = sum(
        int(l.size) for p, l in _j.tree_util.tree_flatten_with_path(
            m0.estimator.variables["params"])[0]
        if "embed" not in "/".join(str(q) for q in p).lower())
    c = m0._config
    fpt = 6 * p_dense + 12 * c["n_block"] * c["hidden_size"] * SEQ

    results = {name: [] for name, _, _ in cfgs}
    for r in range(rounds):
        for name, batch, accum in cfgs:
            tps = window(models[name][0], models[name][1],
                         models[name][2], batch)
            mfu = tps * fpt / PEAK
            results[name].append(mfu)
            print(f"round {r} {name}: {mfu:.4f}", flush=True)
    out = {}
    for name in results:
        s = sorted(results[name])
        out[name] = {"best": round(s[-1], 4),
                     "median": round(s[len(s) // 2], 4)}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
