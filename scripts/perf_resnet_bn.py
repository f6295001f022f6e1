#!/usr/bin/env python
"""ResNet-50 BN-statistics attack A/B (VERDICT r4 item 6): exact
full-batch BN vs sampled stats (zoo.models.bn_stat_rows), interleaved
fit-loop windows in one process. The r4 trace put the BN stat reduce
at 30 ms of a 99 ms step (31%, pure HBM bandwidth); rows=64 of 256
should cut that pass ~4x.

Usage: python scripts/perf_resnet_bn.py [rounds] [rows...]
"""
import json
import sys
import time

import numpy as np

sys.path.insert(0, "/root/repo")

BATCH, STEPS = 256, 8
TRAIN_FLOPS_PER_IMG = 3 * 4.1e9
PEAK = 197e12


def run_config(rows, epochs):
    """ONE fit call per config: per-epoch seconds come from the fit
    history (epoch 1 = compile, excluded). A fit call re-uploads the
    dataset, so windows-per-fit-call would time the upload, not the
    training step."""
    from analytics_zoo_tpu.common.config import get_config
    from analytics_zoo_tpu.models.image.classifier import ImageClassifier

    cfg = get_config()
    cfg.set("zoo.train.log_every_n_steps", 100000)
    # read at TRACE time (like zoo.ops.attention_impl) -- set through
    # this model's compile
    cfg.set("zoo.models.bn_stat_rows", rows)
    rng = np.random.RandomState(0)
    n = BATCH * STEPS
    x = rng.rand(n, 224, 224, 3).astype(np.float32)  # match bench.py
    y = rng.randint(0, 1000, n).astype(np.int32)
    model = ImageClassifier(class_num=1000, backbone="resnet50",
                            dtype="bfloat16")
    hist = model.fit((x, y), batch_size=BATCH, epochs=epochs,
                     device_cache=True)
    secs = sorted(h["seconds"] for h in hist[1:])
    mfus = [(n / s) * TRAIN_FLOPS_PER_IMG / PEAK for s in secs]
    return {"best": round(max(mfus), 4),
            "median": round(mfus[len(mfus) // 2], 4),
            "epoch_s": [round(s, 3) for s in secs]}


def main():
    epochs = (int(sys.argv[1]) if len(sys.argv) > 1 else 5) + 1
    rows_list = [int(a) for a in sys.argv[2:]] or [0, 64]
    out = {}
    for rows in rows_list:
        print(f"running rows={rows} ...", flush=True)
        out[str(rows)] = run_config(rows, epochs)
        print(f"rows={rows}: {out[str(rows)]}", flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
