#!/usr/bin/env python
"""Chaos soak driver: the serving pipeline under a seeded fault
schedule (ISSUE-5).

Drives the REAL data plane (InputQueue -> supervised ServingWorker ->
OutputQueue, fast wire codec, InferenceModel bucketed predict) while
the chaos harness (serving/chaos.py) injects crashes, stalls, errors
and dropped replies at the engine's stage seams -- randomized but
SEEDED, so a failing soak replays exactly with the same --seed/--spec.

What "pass" looks like: every request the chaos schedule did not
explicitly destroy (dropped replies) is answered exactly once -- as a
result or a structured error -- without operator action, across
however many supervisor restarts the schedule forces.

Prints one JSON line:
  {"requests", "answered", "ok", "errors", "deadline_exceeded",
   "duplicates", "unanswered", "restarts", "injected", "elapsed_s",
   "rps", "seed", "spec", "recovered"}
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

FEATURES = 16
DEFAULT_SPEC = ("crash:dispatch:at=25;crash:decode:at=70;"
                "sleep:finalize:p=0.01:dur=0.05;"
                "error:dispatch:p=0.01;drop:push:p=0.005")


def build_model():
    import flax.linen as nn
    import jax

    from analytics_zoo_tpu.inference.inference_model import (
        InferenceModel, bucket_ladder)

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(8)(nn.relu(nn.Dense(32)(x)))

    net = Net()
    variables = net.init(jax.random.PRNGKey(0),
                         np.zeros((1, FEATURES), np.float32))
    model = InferenceModel().load_flax(net, variables=variables)
    model.warm_up(np.zeros((1, FEATURES), np.float32),
                  batch_sizes=tuple(bucket_ladder(32)))
    return model


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spec", default=DEFAULT_SPEC,
                    help="chaos schedule (kind:seam[:k=v]*;...); "
                         "with --replicas, kill:replica:at=K entries "
                         "SIGKILL whole replica processes")
    ap.add_argument("--deadline-ms", type=float, default=30000.0)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--drain-timeout", type=float, default=60.0)
    ap.add_argument("--replicas", type=int, default=0,
                    help="> 1 runs the soak against a real replica "
                         "FLEET (delegates to fleet_soak.py: consumer"
                         "-group sharding, SIGKILL of whole replicas, "
                         "rolling restart)")
    args = ap.parse_args()

    if args.replicas and args.replicas > 1:
        # replica-level chaos lives in the fleet driver -- one soak
        # convention, two granularities. The fleet driver honors
        # requests/seed/drain-timeout and kill:replica spec entries
        # ONLY; say so instead of silently eating the others.
        import fleet_soak

        dropped = []
        if args.deadline_ms != 30000.0:
            dropped.append("--deadline-ms")
        if args.batch_size != 8:
            dropped.append("--batch-size")
        in_process = [e for e in args.spec.split(";")
                      if e.strip() and "replica" not in e]
        if in_process:
            dropped.append(f"spec entries {';'.join(in_process)!r} "
                           "(in-process seams only arm in single-"
                           "worker mode)")
        if dropped:
            print("chaos_serving --replicas: ignoring "
                  + ", ".join(dropped), file=sys.stderr)
        sys.argv = [sys.argv[0],
                    "--requests", str(args.requests),
                    "--replicas", str(args.replicas),
                    "--seed", str(args.seed),
                    "--drain-timeout", str(args.drain_timeout)]
        replica_entries = ";".join(
            e for e in args.spec.split(";")
            if e.strip() and "replica" in e)
        if replica_entries:
            sys.argv += ["--spec", replica_entries]
        fleet_soak.main()
        return

    from analytics_zoo_tpu.serving import chaos
    from analytics_zoo_tpu.serving.queues import (
        InputQueue, OutputQueue)
    from analytics_zoo_tpu.serving.resilience import Supervisor
    from analytics_zoo_tpu.serving.worker import (
        DEADLINE_PREFIX, ERROR_KEY, ServingWorker)

    model = build_model()
    rng = np.random.RandomState(args.seed)
    xs = rng.randn(256, FEATURES).astype(np.float32)

    in_q = InputQueue(maxlen=args.requests + 10,
                      deadline_ms=args.deadline_ms)
    out_q = OutputQueue()
    for i in range(args.requests):
        assert in_q.enqueue(f"c{i:06d}", x=xs[i % len(xs)])

    injector = chaos.install(chaos.ChaosInjector(
        chaos.parse_spec(args.spec), seed=args.seed))
    worker = ServingWorker(model, in_q, out_q,
                           batch_size=args.batch_size, timeout_ms=2.0,
                           max_batch_size=32)
    sup = Supervisor(worker, poll_interval_s=0.05,
                     heartbeat_timeout_s=2.0, backoff_base_s=0.02,
                     backoff_max_s=0.5, seed=args.seed)
    t0 = time.perf_counter()
    worker.start()
    sup.start()
    replies = []
    seen = set()
    deadline = time.time() + args.drain_timeout
    try:
        while len(seen) < args.requests and time.time() < deadline:
            item = out_q.dequeue(timeout=0.1)
            if item is not None:
                replies.append(item)
                seen.add(item[0])
    finally:
        elapsed = time.perf_counter() - t0
        sup.stop()
        worker.stop()
        chaos.uninstall()

    ok = errors = deadlines = 0
    for _, tensors in replies:
        if ERROR_KEY not in tensors:
            ok += 1
        elif str(tensors[ERROR_KEY]).startswith(DEADLINE_PREFIX):
            deadlines += 1
        else:
            errors += 1
    injected = injector.counts()
    dropped = injected.get("push:drop", 0)
    unanswered = args.requests - len(seen)
    line = {
        "requests": args.requests,
        "answered": len(seen),
        "ok": ok,
        "errors": errors,
        "deadline_exceeded": deadlines,
        "duplicates": len(replies) - len(seen),
        "unanswered": unanswered,
        "dropped_by_chaos": dropped,
        "restarts": sup.restarts,
        "injected": injected,
        "elapsed_s": round(elapsed, 3),
        "rps": round(len(seen) / max(elapsed, 1e-9), 1),
        "seed": args.seed,
        "spec": args.spec,
        # recovery verdict: everything the schedule didn't destroy
        # (dropped replies, or replies racing the final drain cutoff)
        # was answered; restarts happened if the spec forced any
        "recovered": unanswered <= dropped,
    }
    print(json.dumps(line))
    sys.exit(0 if line["recovered"] else 1)


if __name__ == "__main__":
    main()
