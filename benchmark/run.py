"""One cell, one run:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Driven by data. The cell's entry in ``BENCHMARK.json`` names its
configuration; ``workloads/<cell>.json`` holds the traffic and names the
runner; ``configs/<config>.json`` holds the sizes, the model's factory
and its plain reference; ``runners/<runner>.py`` drives the program;
``layer_metrics/<name>.py`` reads one per-layer metric each
(``read(ctx) -> number or None``). A new cell, configuration, runner or
metric is new files plus entries in ``BENCHMARK.json``.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``). Everything else goes to standard
error. Without a TPU, or with fewer chips than the cell asks for, the
command fails and prints no result -- except under
``ZOO_BENCH_REHEARSAL=1``, which runs the cell's tiny ``rehearsal``
sizes on whatever backend there is and reports no timing at all.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")


def fail(message: str) -> "NoReturn":  # noqa: F821
    print(f"benchmark: {message}", file=sys.stderr)
    raise SystemExit(3)


def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        fail(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def metrics_for(entries: list, cell: str) -> list:
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    rehearsal = os.environ.get("ZOO_BENCH_REHEARSAL") == "1"

    bench = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        fail(f"no cell {args.workload!r} in BENCHMARK.json")
    cell = load_json(BENCH_DIR, "workloads", f"{entry['name']}.json")
    config = load_json(BENCH_DIR, "configs", f"{entry['config']}.json")

    # the compile cache: where the environment says, else a fixed place
    # in the checkout. The program honours the variable too. Caching
    # every program (no 1 s floor) is what keeps a warm set-up short.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(CACHE_DIR, "xla"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    import jax

    devices = jax.devices()
    chips = int(entry["chips"])
    if not rehearsal and devices[0].platform != "tpu":
        fail(f"no TPU: jax found platform {devices[0].platform!r}")
    if len(devices) < chips:
        fail(f"cell needs {chips} chips, jax found {len(devices)}")

    from benchmark.lib.compile_watch import CompileWatch

    window_start = []      # [(clock, compile accounting)] at the window's start
    phases = []            # [(set-up phase, seconds since process start)]
    spec = types.SimpleNamespace(
        cell=cell, config=config, chips=chips, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), rehearsal=rehearsal,
        watch=CompileWatch(),
        scratch_dir=os.path.join(CACHE_DIR, "scratch", entry["name"]),
        phase=lambda name: phases.append(
            (name, time.perf_counter() - T_PROCESS)),
        mark_window_start=lambda: window_start.append(
            (time.perf_counter(), spec.watch.snapshot())))
    spec.phase("imports_and_backend")
    runner = importlib.import_module(f"benchmark.runners.{cell['runner']}")
    result = runner.run(spec)
    started, setup_compiles = window_start[0]
    result["end_to_end"]["setup_s"] = started - T_PROCESS

    declared = metrics_for(
        bench["per_layer" if args.trace else "end_to_end"], entry["name"])
    metrics, missing = {}, []
    if args.trace:
        ctx = dict(result["ctx"], cell=cell, config=config, chips=chips,
                   device_kind=devices[0].device_kind,
                   trace=result["trace"], rehearsal=rehearsal)
        reported = {m["name"] for m in metrics_for(bench["end_to_end"],
                                                   entry["name"])}
        for m in declared:
            if m["moves"] not in reported:
                continue
            reader = importlib.import_module(
                f"benchmark.layer_metrics.{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in declared:
            if m["name"] in result["end_to_end"]:
                metrics[m["name"]] = {"value": result["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
            else:
                missing.append(m["name"])
    if missing:
        fail(f"runner {cell['runner']!r} reported no {missing}")
    if rehearsal:
        # a CPU run has no timing worth a name: counts only
        metrics = {k: v for k, v in metrics.items()
                   if v["unit"] == "count"}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}
    trace = result["trace"]
    if args.trace and not rehearsal:
        if not trace:
            fail("traced run found no device operation in its trace")
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["ops"],
                             "idle_gaps": trace["idle_gaps"]}
    print(json.dumps({"detail": result["detail"],
                      "setup_compile_requests": setup_compiles,
                      "setup_phases_done_at_s": phases}),
          file=sys.stderr)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
