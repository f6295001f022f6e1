"""Runner ``train_fit``: a zoo model trained through ``model.fit`` on
seeded host arrays, one epoch per call, until the window is full.
``train_samples_per_s`` is an epoch's samples over the MEDIAN of the
epochs' seconds, each epoch one reading on the host's clock.

The program is used as a trainer uses it: the model class from the
configuration file, ``compile(optimizer, mesh)``, ``fit((x, y),
batch_size, epochs)`` with the input pipeline running (host arrays ->
``device_iterator`` -> ``shard_batch``), the default logging cadence,
buffer donation and dropout as shipped. Every epoch ends in the
program's own host sync (``float(loss_sum)``), so the window's clock
stops on finished device work.

Set-up: data from the seed, weights made on the device by ONE jitted
``init`` from the seed, optimizer state, and a short warm-up ``fit`` on
the first rows (same batch shape, so it compiles the step program the
window uses). After the window and outside every clock: with
``--trace 1`` one more epoch inside the benchmark's own profiler trace
(device planes only), then the comparison with the plain reference.
"""

from __future__ import annotations

import importlib
import math
import os
import shutil
import statistics
import time

from benchmark.lib import trace_reduce, traffic


def _trace_options():
    """Device operations only. With the host tracer on (at any level)
    the runtime records one event per 672-byte row of every image it
    re-tiles for the device: 16 ResNet steps fed from the host wrote
    16 million events (a 500 MB trace) and stalled two steps by 3-4 s
    each (PR 22) -- a trace of the tracer. So the traced window is one
    whole ``fit`` call of one epoch timed on the host's clock, and the
    trace gives the device's side of it."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    return options


def resolve(dotted: str):
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def _build(config: dict, part: str):
    """``config[part]``: a dotted factory, keyword arguments read from
    the configuration's own top-level keys (``kwargs_from``, so a size
    is written once) and literal ones (``kwargs``)."""
    p = config[part]
    kwargs = {k: config[v] for k, v in p.get("kwargs_from", {}).items()}
    return resolve(p["factory"])(**kwargs, **p.get("kwargs", {}))


def samples_per_s(epoch_seconds: list, samples_per_epoch: int) -> float:
    """One reading per epoch (a whole ``fit`` call, from its first line
    to the return of its last host sync), and the median of them. The
    host of a one-chip machine shares its cores: a neighbour's burst
    stalls an epoch or two of a run by tenths of a second, which a
    total over the window carries in full (the driver's first check of
    PR 22 read runs several percent off in cells whose quiet runs agree
    to 0.02 %) and the median of the epochs does not."""
    return samples_per_epoch / statistics.median(epoch_seconds)


def _rows(tree, n: int):
    import jax

    return jax.tree_util.tree_map(lambda a: a[:n], tree)


def _memory(devices) -> dict:
    """Peak bytes on the fullest chip. On this runtime
    ``peak_bytes_in_use`` covers live arrays only; a running program's
    scratch is *reserved* and shows in ``peak_bytes_reserved`` (probe,
    PR 22: a program with 512 MiB of temporaries left exactly
    536,870,912 there). Their sum is the peak as long as the live
    arrays are steady while the step runs, which they are in ``fit``."""
    live = reserved = 0
    for d in devices:
        stats = d.memory_stats() or {}
        live = max(live, int(stats.get("peak_bytes_in_use", 0)))
        reserved = max(reserved, int(stats.get("peak_bytes_reserved", 0)))
    return {"live_peak_bytes": live, "reserved_peak_bytes": reserved,
            "peak_bytes": live + reserved}


def run(spec) -> dict:
    import jax
    import numpy as np

    from analytics_zoo_tpu.obs.metrics import get_registry
    from analytics_zoo_tpu.parallel import create_mesh

    cell, config = spec.cell, spec.config
    data = dict(cell["data"])
    if spec.rehearsal:
        data.update(cell.get("rehearsal", {}))
        config = {**config, **config.get("rehearsal", {})}
    batch, steps = int(data["batch"]), int(data["steps_per_epoch"])
    devices = jax.devices()[:spec.chips]
    mesh = create_mesh({"data": len(devices)}, devices=devices)

    # ---------------------------------------------------------- set-up --
    x, y = traffic.generate(data, config, spec.seed)
    spec.phase("data")
    model = _build(config, "model")
    init = jax.jit(model.estimator.adapter.init)
    model.estimator.variables = init(jax.random.PRNGKey(spec.seed),
                                     _rows(x, 1))
    spec.phase("model_and_weights")
    model.compile(optimizer=_build(config, "optimizer"), mesh=mesh,
                  seed=spec.seed)
    est = model.estimator
    warm = int(data["warmup_steps"]) * batch
    model.fit((_rows(x, warm), y[:warm]), batch_size=batch,
              epochs=est.epoch + 1)
    spec.phase("warmup_fit")

    # ---------------------------------------------------------- window --
    steps_counter = get_registry().get("zoo_learn_steps_total")
    counted_before = steps_counter.value
    compiles_before = spec.watch.snapshot()
    losses, epoch_s, data_wait_s = [], [], 0.0
    spec.mark_window_start()
    t0 = t_epoch = time.perf_counter()
    while True:
        history = model.fit((x, y), batch_size=batch, epochs=est.epoch + 1,
                            profile=spec.trace)
        now = time.perf_counter()
        epoch_s.append(now - t_epoch)
        t_epoch = now
        losses.append(float(history[0]["loss"]))
        if spec.trace:
            data_wait_s += est.last_profile.summary()[
                "data_wait"]["total_s"]
        window_s = time.perf_counter() - t0
        if window_s >= spec.seconds:
            break
    compiles = spec.watch.since(compiles_before)
    counted = int(steps_counter.value - counted_before)
    memory = _memory(devices)

    # ---------------------------------------------------- traced epoch --
    trace = None
    if spec.trace:
        # one trace per cell is kept (and replaced by the next traced run)
        trace_dir = os.path.join(spec.scratch_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
        try:
            t_fit = time.perf_counter()
            model.fit((x, y), batch_size=batch, epochs=est.epoch + 1)
            traced_fit_s = time.perf_counter() - t_fit
        finally:
            jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(trace_dir)
        if path:
            trace = trace_reduce.reduce_trace(
                trace_reduce.load_xplane(path), window_s=traced_fit_s)

    # ----------------------------------------------------- correctness --
    ref = config["reference"]
    n_check = int(ref["rows"])
    got = model.predict(_rows(x, n_check), batch_size=n_check)
    want = resolve(ref["forward"])(
        jax.device_get(est.variables), _rows(x, n_check), config)
    got, want = (np.concatenate(
        [np.asarray(leaf, np.float32).ravel()
         for leaf in jax.tree_util.tree_leaves(tree)]) for tree in (got, want))
    ref_error = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    ref_error_max = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    bad_epochs = sum(1 for v in losses if not math.isfinite(v))
    checks = {
        "every_epoch_loss_finite": bad_epochs == 0,
        "last_epoch_loss_below_first": (len(losses) > 1
                                        and losses[-1] < losses[0]),
        "reference_within_tolerance": ref_error <= float(ref["tolerance"]),
        "steps_counted_equal_steps_run": counted == len(losses) * steps,
    }
    work = resolve(config["flops"])(config, data)
    n_steps = len(losses) * steps
    rate = samples_per_s(epoch_s, steps * batch)
    return {
        "correct": all(checks.values()),
        "attempted": n_steps,
        "failed": bad_epochs * steps,
        "end_to_end": {"train_samples_per_s": rate},
        "memory_peak_bytes": memory["peak_bytes"],
        "trace": trace,
        "ctx": {
            "window": {"seconds": window_s, "steps": n_steps,
                       "samples": n_steps * batch, "epochs": len(losses),
                       "samples_per_s": rate,
                       "steps_per_epoch": steps, "batch": batch},
            "compiles_in_window": compiles,
            "data_wait_s": data_wait_s,
            "work": work,
            "memory": memory,
        },
        "detail": {"checks": checks, "epoch_losses": losses,
                   "epoch_seconds": epoch_s,
                   "samples_per_s_over_whole_window":
                       n_steps * batch / window_s,
                   "reference_error": ref_error,
                   "reference_error_max_norm": ref_error_max,
                   "reference_tolerance": float(ref["tolerance"])},
    }
