#!/usr/bin/env python
"""Multichip harness: weak-scaling efficiency + sharded-serving A/B.

Two modes, one contract (the bench.py convention): the final stdout
line ALWAYS parses as JSON, and an unavailable backend or any mid-run
crash ends in an ``error`` line AND a non-zero exit code:

**Default** -- WEAK scaling of the NCF SPMD train step (BASELINE
north-star #3: 8->64-chip scaling efficiency, target >90% on v5e-64):
per-device batch held constant, throughput per device compared against
the single-device run. On real multi-chip hardware this reports the
ICI/DCN allreduce efficiency.

**--serving** -- SERVING throughput through the real pipelined engine
(InputQueue -> ServingWorker -> OutputQueue) for a TP-shardable
transformer, A/B'd across ``zoo.serving.shard.mode`` off / tp / dp
(plus tp with quantized collectives), at two model sizes -- the
(model size x mode) crossover table of BENCH_NOTES.md. Reports
sustained saturation rps per mode and client-observed p50/p99 at one
matched offered load per size.

Either mode runs on real chips or, without hardware, on a CPU
host-device mesh: ``--virtual N`` sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the tier-1/CI
smoke path -- it validates the SPMD/sharded-dispatch code, not
interconnect performance).

Final line, default mode:
  {"metric": "scaling_efficiency", "value": <eff at max size>, ...}
Final line, --serving:
  {"metric": "serving_shard_ab", "value": <tp/off rps ratio, big>, ...}
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


# backend init (None instead of raising): ONE implementation, shared
# with bench.py, so the two harnesses' final-line contracts cannot drift
from bench import _init_backend  # noqa: E402


# ------------------------------------------------------------------ #
# default mode: weak-scaling efficiency (north-star #3)               #
# ------------------------------------------------------------------ #
def measure(mesh_devices, per_device_batch: int, steps: int = 20):
    import jax
    import numpy as np

    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.models.recommendation.ncf import NeuralCF
    from analytics_zoo_tpu.parallel import create_mesh
    from analytics_zoo_tpu.parallel.sharding import replicated

    n_dev = len(mesh_devices)
    mesh = create_mesh({"data": n_dev}, devices=mesh_devices)
    model = NeuralCF(6040, 3706, class_num=5)
    est = Estimator(model.module, loss=model.default_loss,
                    optimizer="adam", mesh=mesh)
    batch = per_device_batch * n_dev
    rng = np.random.RandomState(0)
    x = np.stack([rng.randint(1, 6041, batch),
                  rng.randint(1, 3707, batch)], 1).astype(np.int32)
    y = rng.randint(1, 6, batch).astype(np.int32)
    est._ensure_built(x[:8])
    step = est._build_train_step()
    from analytics_zoo_tpu.parallel.sharding import shard_batch

    xb = shard_batch(x, mesh)
    yb = shard_batch(y, mesh)
    import jax.numpy as jnp

    # placed like the step's own output, or the first timed step would
    # trace and compile again for the mesh-resident scalar's type
    loss_sum = jax.device_put(jnp.zeros((), jnp.float32),
                              replicated(mesh))
    key = jax.random.PRNGKey(0)
    # warm-up (compile)
    v, o, loss_sum, _ = step(est.variables, est.opt_state, loss_sum,
                             xb, yb, key)
    jax.block_until_ready(loss_sum)
    t0 = time.perf_counter()
    for i in range(steps):
        v, o, loss_sum, _ = step(v, o, loss_sum, xb, yb,
                                 jax.random.fold_in(key, i))
    jax.block_until_ready(loss_sum)
    dt = time.perf_counter() - t0
    return steps * batch / dt / n_dev  # samples/sec/device


def run_scaling(args, devices) -> dict:
    sizes = [s for s in (1, 2, 4, 8, 16, 32, 64) if s <= len(devices)]
    points = {}
    for s in sizes:
        points[s] = measure(devices[:s], args.per_device_batch)
    base = points[sizes[0]]
    eff = {s: round(v / base, 4) for s, v in points.items()}
    return {
        "metric": "scaling_efficiency",
        "value": eff[sizes[-1]],
        "unit": "fraction_of_linear",
        "extras": {
            "per_device_batch": args.per_device_batch,
            "samples_per_sec_per_device": {
                str(s): round(v, 1) for s, v in points.items()},
            "efficiency": {str(s): e for s, e in eff.items()},
            "note": ("virtual CPU devices (harness validation), not "
                     "interconnect perf" if args.virtual else
                     "real devices"),
        },
    }


# ------------------------------------------------------------------ #
# --serving mode: sharded serving throughput A/B                      #
# ------------------------------------------------------------------ #
SIZES = {
    # (vocab, seq_len, hidden, heads, blocks): "small" is the
    # dp-favored regime (tiny params, collective overhead dominates tp),
    # "big" is the tp-favored one on real chips (matmul-bound forward,
    # 1/N params per chip)
    "small": dict(vocab=64, seq_len=16, hidden_size=32, n_head=2,
                  n_block=2),
    "big": dict(vocab=256, seq_len=32, hidden_size=256, n_head=4,
                n_block=4),
}
SERVING_BATCH = 16
SERVING_MAX_BATCH = 64
SERVING_DEPTH = 2


def _build_serving_model(size_cfg, mode: str, quantized: bool):
    """A fresh InferenceModel on the size's transformer, shard plan
    attached per config, warmed under the active mesh."""
    import jax
    import numpy as np

    from analytics_zoo_tpu.common.config import get_config
    from analytics_zoo_tpu.inference.inference_model import (
        InferenceModel, bucket_ladder)
    from analytics_zoo_tpu.keras.layers.transformer import (
        TransformerModule)

    cfg = get_config()
    cfg.set("zoo.serving.shard.mode", mode)
    cfg.set("zoo.serving.shard.quantized_collectives", quantized)
    module = TransformerModule(hidden_dropout=0.0, attn_dropout=0.0,
                               **size_cfg)
    ids = np.zeros((1, size_cfg["seq_len"]), np.int32)
    variables = module.init(jax.random.PRNGKey(0), ids)
    model = InferenceModel().load_flax(module, variables=variables)
    model.shard()  # resolves the config (no-op at mode=off)
    model.warm_up(ids, batch_sizes=tuple(bucket_ladder(
        SERVING_MAX_BATCH)))
    return model


def _saturation(model, n_requests: int, xs) -> float:
    """Pre-filled queue -> drain-everything rps through the pipelined
    engine (the perf_serving_pipeline saturation phase)."""
    from analytics_zoo_tpu.serving.queues import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.worker import ServingWorker

    in_q, out_q = InputQueue(maxlen=n_requests + 10), OutputQueue()
    for i in range(n_requests):
        assert in_q.enqueue(f"r{i}", x=xs[i % len(xs)])
    worker = ServingWorker(model, in_q, out_q,
                           batch_size=SERVING_BATCH,
                           max_batch_size=SERVING_MAX_BATCH,
                           pipeline_depth=SERVING_DEPTH,
                           pipelined=True)
    backend = out_q.queue
    t0 = time.perf_counter()
    worker.start()
    done = 0
    # bounded drain: a wedged worker must surface as the error JSON
    # line (the __main__ guard), never as a silent hang -- the exact
    # contract this harness exists to keep
    deadline = t0 + 300.0
    while done < n_requests and time.perf_counter() < deadline:
        got = backend.get_many(512)
        done += len(got)
        if not got:
            time.sleep(0.002)
    dt = time.perf_counter() - t0
    worker.stop()
    if done < n_requests:
        raise RuntimeError(
            f"saturation window wedged: {done}/{n_requests} answered "
            f"in {dt:.0f}s")
    return n_requests / dt


def _matched_load(model, rps: float, seconds: float, xs):
    """Paced offered load; client-observed (p50_ms, p99_ms,
    achieved_rps)."""
    from analytics_zoo_tpu.serving.queues import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.worker import ServingWorker

    in_q, out_q = InputQueue(maxlen=100000), OutputQueue()
    worker = ServingWorker(model, in_q, out_q,
                           batch_size=SERVING_BATCH,
                           max_batch_size=SERVING_MAX_BATCH,
                           pipeline_depth=SERVING_DEPTH,
                           pipelined=True).start()
    try:
        sent, done = {}, {}
        t_start = time.perf_counter()
        t_end = t_start + seconds
        i = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            owed = int((now - t_start) * rps) - i
            for _ in range(max(0, owed)):
                uri = f"m{i}"
                in_q.enqueue(uri, x=xs[i % len(xs)])
                sent[uri] = time.perf_counter()
                i += 1
            for uri, _t in out_q.dequeue_all():
                done[uri] = time.perf_counter()
            time.sleep(0.0005)
        deadline = time.perf_counter() + 15.0
        while len(done) < len(sent) and time.perf_counter() < deadline:
            for uri, _t in out_q.dequeue_all():
                done[uri] = time.perf_counter()
            time.sleep(0.001)
    finally:
        worker.stop()
    lats = sorted(done[u] - sent[u] for u in done if u in sent)
    if not lats:
        return None, None, 0.0
    p50 = lats[len(lats) // 2] * 1e3
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))] * 1e3
    # achieved = completions INSIDE the offered window; the post-window
    # drain still feeds the latency percentiles (that lateness is
    # exactly what p99 must show) but must not inflate the rate
    in_window = sum(1 for t in done.values() if t <= t_end)
    return p50, p99, in_window / seconds


def run_serving(args, devices) -> dict:
    import numpy as np

    from analytics_zoo_tpu.common.config import get_config

    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    sizes = [s.strip() for s in args.sizes.split(",") if s.strip()]
    cfg = get_config()
    table: dict = {}
    for size in sizes:
        size_cfg = SIZES[size]
        rng = np.random.RandomState(0)
        xs = rng.randint(0, size_cfg["vocab"],
                         (256, size_cfg["seq_len"])).astype(np.int32)
        table[size] = {}
        models = {}
        for mode in modes:
            quantized = mode == "tp_q8"
            shard_mode = "tp" if quantized else mode
            model = _build_serving_model(size_cfg, shard_mode,
                                         quantized)
            # throwaway window: thread/alloc spin-up out of the timing
            _saturation(model, min(100, args.serving_requests), xs)
            rps = max(_saturation(model, args.serving_requests, xs)
                      for _ in range(args.windows))
            models[mode] = model
            table[size][mode] = {"rps": round(rps, 1)}
        # ONE offered load per size, anchored on the OFF-mode
        # saturation point (first listed mode only when off is not
        # measured) so every mode faces the same demand
        anchor = table[size].get("off") or table[size][modes[0]]
        matched_rps = max(20.0, 0.5 * anchor["rps"])
        for mode in modes:
            p50, p99, ach = _matched_load(models[mode], matched_rps,
                                          args.matched_seconds, xs)
            table[size][mode].update({
                "p50_ms": None if p50 is None else round(p50, 2),
                "p99_ms": None if p99 is None else round(p99, 2),
                "matched_rps_offered": round(matched_rps, 1),
                "matched_rps_achieved": round(ach, 1),
            })
            print(f"serving[{size}] mode={mode}: {table[size][mode]}",
                  file=sys.stderr)
        models.clear()
    for key in ("zoo.serving.shard.mode",
                "zoo.serving.shard.quantized_collectives"):
        cfg.unset(key)
    big = table.get("big") or table[sizes[0]]
    ratio = (round(big["tp"]["rps"] / big["off"]["rps"], 3)
             if "tp" in big and "off" in big else None)
    return {
        "metric": "serving_shard_ab",
        "value": ratio,
        "unit": "tp_over_off_rps_ratio",
        "extras": {
            "table": table,
            "n_devices": len(devices),
            "cores": os.cpu_count(),
            "batch": SERVING_BATCH,
            "max_batch": SERVING_MAX_BATCH,
            "note": ("virtual CPU devices over "
                     f"{os.cpu_count()} host core(s): validates the "
                     "sharded dispatch path; mode ratios are host-"
                     "scheduling artifacts, not interconnect perf"
                     if args.virtual else "real devices"),
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--virtual", type=int, default=None,
                    help="force N virtual CPU host devices (the "
                         "hardware-free tier-1/CI mesh)")
    ap.add_argument("--per-device-batch", type=int, default=8192)
    ap.add_argument("--serving", action="store_true",
                    help="measure sharded SERVING throughput instead "
                         "of train-step weak scaling")
    ap.add_argument("--modes", default="off,tp,dp,tp_q8",
                    help="comma list of shard modes for --serving")
    ap.add_argument("--sizes", default="small,big",
                    help="comma list of model sizes for --serving")
    ap.add_argument("--serving-requests", type=int, default=2000,
                    help="requests per saturation window")
    ap.add_argument("--windows", type=int, default=2,
                    help="saturation windows per mode (best kept)")
    ap.add_argument("--matched-seconds", type=float, default=4.0)
    args = ap.parse_args()
    if args.virtual:
        # both are read at the first backend init, which has not
        # happened yet: a --virtual run never asks for the chip (it is
        # bench.py's child while bench.py holds it)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.virtual}"
        ).strip()
    devices = _init_backend()
    if devices is None:
        print(json.dumps({"value": None,
                          "error": "backend_unavailable"}))
        sys.exit(1)
    print(json.dumps(run_serving(args, devices) if args.serving
                     else run_scaling(args, devices)))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # guaranteed parseable final line (the
        # driver's contract): a mid-run crash exits non-zero, but
        # never in a bare traceback
        import traceback

        traceback.print_exc()
        print(json.dumps({"value": None,
                          "error": f"{type(e).__name__}: {e}"[:200]}))
        sys.exit(1)
