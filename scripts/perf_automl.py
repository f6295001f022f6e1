#!/usr/bin/env python
"""Vectorized AutoML A/B (ISSUE-13).

Runs the SAME zouwu time-series search (fixed LSTM architecture, an
lr grid -- one shape-compatible cohort) through two executors:

- **vectorized**: every trial is a lane of ONE vmapped population --
  the whole sweep is a handful of XLA dispatches;
- **process**: the reference shape, one trial per spawn-pool worker
  (the pool also replays the sequential per-trial semantics, so its
  rewards double as the parity baseline).

Headline: trials/sec each way + the speedup. Gates (exit nonzero on
failure):

- **parity**: per-trial rewards match across executors to float
  tolerance (same sampled configs by seed; a population lane replays
  the solo Estimator trajectory by construction);
- **one-cohort**: the vectorized run dispatched exactly one cohort
  (fixed arch + lr-only variation must not split);
- **no fallback**: no trial escaped to the sequential rescue path.

Prints ONE JSON line.
CPU-rig caveats in BENCH_NOTES.md: absolute trials/sec is hardware-
dependent; the parity gates and the vectorized-vs-pool ratio are the
committed signal (AUTOML_r01.json).
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def make_data(n):
    import pandas as pd

    rng = np.random.RandomState(7)
    dt = pd.date_range("2020-01-01", periods=n, freq="1h")
    value = (np.sin(np.arange(n) * 2 * np.pi / 24)
             + 0.1 * rng.randn(n)).astype(np.float32)
    df = pd.DataFrame({"datetime": dt, "value": value})
    spec = {"future_seq_len": 1, "dt_col": "datetime",
            "target_col": ["value"], "extra_features_col": None,
            "drop_missing": True}
    return {"spec": spec, "train_df": df.iloc[:int(n * 0.8)],
            "validation_df": df.iloc[int(n * 0.75):]}


def make_space(trials, epochs):
    from analytics_zoo_tpu.automl.space import Grid

    lrs = list(np.geomspace(3e-4, 0.3, trials).astype(float))
    return {"model": "LSTM", "lstm_1_units": 16, "lstm_2_units": 8,
            "dropout_1": 0.2, "dropout_2": 0.2, "lr": Grid(lrs),
            "batch_size": 32, "epochs": epochs,
            "selected_features": ["hour"], "past_seq_len": 6}


def run_search(executor, space, data, workers):
    from analytics_zoo_tpu.automl.predictor import time_sequence_trial
    from analytics_zoo_tpu.automl.search import SearchEngine

    eng = SearchEngine(executor=executor, max_workers=workers)
    eng.compile(data, time_sequence_trial, search_space=dict(space),
                metric="mse", seed=0)
    t0 = time.perf_counter()
    eng.run()
    return eng, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--rows", type=int, default=200)
    ap.add_argument("--workers", type=int,
                    default=min(4, os.cpu_count() or 1))
    ap.add_argument("--tol", type=float, default=1e-5,
                    help="per-trial |mse_vec - mse_pool| parity gate")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: 8 trials, 1 epoch")
    args = ap.parse_args()
    if args.smoke:
        args.trials, args.epochs, args.rows = 8, 1, 160

    from analytics_zoo_tpu.obs.events import get_event_log
    from analytics_zoo_tpu.obs.metrics import get_registry

    data = make_data(args.rows)
    space = make_space(args.trials, args.epochs)

    vec, vec_s = run_search("vectorized", space, data, args.workers)
    pool, pool_s = run_search("process", space, data, args.workers)

    assert [t.config["lr"] for t in vec.trials] == \
        [t.config["lr"] for t in pool.trials], "config plans diverged"
    errors = sum(1 for t in vec.trials + pool.trials
                 if t.error is not None)
    diffs = [abs(a.reward - b.reward)
             for a, b in zip(vec.trials, pool.trials)
             if a.error is None and b.error is None]
    max_diff = max(diffs) if diffs else float("inf")
    cohorts = len({t.extras.get("cohort") for t in vec.trials
                   if t.extras.get("cohort") is not None})
    vec_paths = get_registry().snapshot().get(
        "zoo_automl_vectorized_trials_total", {}).get("values", {})
    fallbacks = int(vec_paths.get("path=fallback", 0))
    train_compiles = len(
        [e for e in get_event_log().tail(type="compile")
         if e.get("fields", {}).get("fn") == "population.train_step"])

    ok = (errors == 0 and max_diff <= args.tol and cohorts == 1
          and fallbacks == 0)
    line = {
        "mode": "perf_automl",
        "trials": args.trials,
        "epochs": args.epochs,
        "rows": args.rows,
        "vectorized_s": round(vec_s, 3),
        "pool_s": round(pool_s, 3),
        "pool_workers": args.workers,
        "vectorized_trials_per_s": round(args.trials / vec_s, 3),
        "pool_trials_per_s": round(args.trials / pool_s, 3),
        "speedup": round(pool_s / vec_s, 2) if vec_s else None,
        "cohorts": cohorts,
        "train_step_compiles": train_compiles,
        "reward_max_abs_diff": max_diff,
        "parity_tol": args.tol,
        "trial_errors": errors,
        "fallback_trials": fallbacks,
        "best_lr": {"vectorized":
                    vec.get_best_trials(1)[0].config["lr"],
                    "pool": pool.get_best_trials(1)[0].config["lr"]},
        "ok": ok,
    }
    print(json.dumps(line))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
