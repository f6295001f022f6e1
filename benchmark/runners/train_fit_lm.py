"""Runner ``train_fit_lm``: ``train_fit`` for a dense causal language
model whose traffic has a generator of its own. ``train_fit.run`` is
tied to ``lib/traffic.generate``'s closed list of kinds and
``train_fit_tokens.run`` judges ``correct`` on expert counters; here the
cell's ``data`` names its generator by dotted path (``"generator"``) and
nothing is asked about experts. The measurement is ``train_fit``'s own,
helper for helper -- one ``fit`` call per epoch, the median of the
epochs' seconds, the same set-up, window, traced epoch and ``ctx`` keys,
so the general per-layer readers work unchanged -- plus:

* ``ctx["gauges"]``: the program's gauges of the ``zoo_model_*_ratio``
  kind as published when the window ends (set while the step is traced:
  functions of the shapes), ``{family: {module: value}}``;
* ``detail["head_losses"]``: each prediction head's mean loss over the
  window, from the growth of the program's
  ``zoo_model_multibyte_head_*_total`` counters (fed from the model's
  device-side ``counters`` collection at each epoch's sync), where the
  model publishes them;
* the comparison is of ``model.predict`` on ``reference.rows`` rows of
  the timed shape with the trained weights against the plain
  reference's logits (relative L2), every prediction head's.

``correct`` = every epoch's loss finite, the last epoch's below the
first's, steps counted = steps run, reference within tolerance. Folding
the three runners into one is a later ``benchmark`` PR's.
"""

from __future__ import annotations

import math
import os
import shutil
import time

from benchmark.lib import trace_reduce
from benchmark.runners.train_fit import (
    _build, _memory, _rows, _trace_options, resolve, samples_per_s)

HEAD_LOSS = "zoo_model_multibyte_head_loss_micronats_total"
HEAD_STEPS = "zoo_model_multibyte_head_steps_total"


def _by_labels(family) -> dict:
    """``{module: {index: value}}`` of one published family (labels
    ``module`` and, on a vector, ``index``)."""
    out = {}
    for labels, value in (family or {"values": {}})["values"].items():
        pairs = dict(p.split("=", 1) for p in labels.split(","))
        out.setdefault(pairs["module"], {})[
            int(pairs.get("index", 0))] = value
    return out


def _published(names) -> dict:
    from analytics_zoo_tpu.obs.metrics import get_registry

    snapshot = get_registry().snapshot()
    return {name: _by_labels(snapshot.get(name)) for name in names}


def _ratio_gauges() -> dict:
    """``{family: {module: value}}`` of the model's ratio gauges."""
    from analytics_zoo_tpu.obs.metrics import get_registry

    return {name: {module: values[0]
                   for module, values in _by_labels(family).items()}
            for name, family in get_registry().snapshot().items()
            if name.startswith("zoo_model_") and name.endswith("_ratio")}


def _head_losses(before: dict, after: dict):
    """Mean loss of each prediction head over the window, in nats;
    ``None`` where the model counts none."""
    steps = sum(v for m in after[HEAD_STEPS].values() for v in m.values()) \
        - sum(v for m in before[HEAD_STEPS].values() for v in m.values())
    if not steps:
        return None
    grown = {}
    for module, values in after[HEAD_LOSS].items():
        for index, value in values.items():
            grown[index] = grown.get(index, 0.0) + value - before[
                HEAD_LOSS].get(module, {}).get(index, 0.0)
    return [grown[i] / steps / 1e6 for i in sorted(grown)]


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
    x, y = resolve(data["generator"])(data, config, spec.seed)
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
    heads_before = _published((HEAD_LOSS, HEAD_STEPS))
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
    head_losses = _head_losses(heads_before,
                               _published((HEAD_LOSS, HEAD_STEPS)))
    gauges = _ratio_gauges()
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
    got = np.asarray(model.predict(_rows(x, n_check), batch_size=n_check),
                     np.float32)
    # the weights stay where they are: the reference reads them as
    # float32 device arrays, which they already are
    want = np.asarray(resolve(ref["forward"])(
        est.variables, _rows(x, n_check), config), np.float32)
    ref_error = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    ref_error_max = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    n_steps = len(losses) * steps
    bad_epochs = sum(1 for v in losses if not math.isfinite(v))
    checks = {
        "every_epoch_loss_finite": bad_epochs == 0,
        "last_epoch_loss_below_first": (len(losses) > 1
                                        and losses[-1] < losses[0]),
        "reference_within_tolerance": ref_error <= float(ref["tolerance"]),
        "steps_counted_equal_steps_run": counted == n_steps,
    }
    work = resolve(config["flops"])(config, data)
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
            "gauges": gauges,
        },
        "detail": {"checks": checks, "epoch_losses": losses,
                   "epoch_seconds": epoch_s,
                   "samples_per_s_over_whole_window":
                       n_steps * batch / window_s,
                   "reference_error": ref_error,
                   "reference_error_max_norm": ref_error_max,
                   "reference_tolerance": float(ref["tolerance"]),
                   "head_losses": head_losses,
                   "gauges": gauges},
    }
