"""Runner ``train_fit_tokens``: ``train_fit`` for a causal language
model with routed experts. It exists because ``train_fit.run`` is tied
to ``lib/traffic.generate``'s closed list of kinds: here the cell's
``data`` names its generator by dotted path (``"generator"``). The
measurement is ``train_fit``'s own, helper for helper -- one ``fit``
call per epoch, the median of the epochs' seconds, the same set-up,
window, traced epoch and ``ctx`` keys, so the general per-layer readers
work unchanged -- plus:

* ``ctx["moe"]``: the growth over the window of the program's
  ``zoo_model_moe_*_total`` counters (``obs.metrics``, fed from the
  model's device-side ``counters`` collection at each epoch's sync);
* ``correct`` also needs no assignment dropped, and every expert
  layer's assignments equal to steps x tokens x experts per token;
* the comparison is of ``model.predict`` on ``reference.rows`` rows of
  the timed shape with the trained weights against the plain
  reference's logits (relative L2), the reference routing for itself;
  the share of assignments on which the two agree is printed.

Folding the two runners into one is a later ``benchmark`` PR's.
"""

from __future__ import annotations

import math
import os
import shutil
import time

from benchmark.lib import trace_reduce
from benchmark.runners.train_fit import (
    _build, _memory, _rows, _trace_options, resolve, samples_per_s)

MOE_COUNTERS = ("moe_assignments", "moe_assignments_held",
                "moe_assignments_dropped", "moe_bias_steps",
                "moe_expert_assignments")


def _moe_counters() -> dict:
    """``{counter: {module: [value per index]}}`` as published so far."""
    from analytics_zoo_tpu.obs.metrics import get_registry

    out, published = {}, get_registry().snapshot()
    for name in MOE_COUNTERS:
        family = published.get(f"zoo_model_{name}_total")
        by_module = out.setdefault(name, {})
        for labels, value in (family or {"values": {}})["values"].items():
            pairs = dict(p.split("=", 1) for p in labels.split(","))
            by_module.setdefault(pairs["module"], {})[
                int(pairs["index"])] = value
    return {name: {m: [v[i] for i in sorted(v)] for m, v in mods.items()}
            for name, mods in out.items()}


def _growth(before: dict, after: dict) -> dict:
    return {name: {m: [a - b for a, b in zip(
        values, before.get(name, {}).get(m, [0] * len(values)))]
        for m, values in mods.items()} for name, mods in after.items()}


def _routing_agreement(est, x, ref_routing, top_k: int):
    """Share of the reference's (token, expert) assignments that the
    program's router makes too, on the rows compared. The program's
    router outputs are read off its modules (``capture_intermediates``);
    ``None`` where the model has no router."""
    import jax
    import jax.numpy as jnp

    module = est.adapter.module

    def routers(variables, x):
        _, state = module.apply(
            variables, x, capture_intermediates=lambda m, _:
            m.name == "router", mutable=["intermediates"])
        return state["intermediates"]

    captured = jax.jit(routers)(est.variables, x)
    agree = total = 0
    layers = sorted(captured, key=lambda name: int(name.rsplit("_", 1)[1]))
    for name, want in zip(layers, ref_routing):
        logits = captured[name]["moe"]["router"]["__call__"][0]
        bias = est.variables["router_state"][name]["moe"]["bias"]
        _, got = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, top_k)
        got = got.reshape(want.shape)
        agree += int(jnp.sum(got[..., :, None] == want[..., None, :]))
        total += want.size
    return agree / total if total else None


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
    moe_before = _moe_counters()
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
    moe = _growth(moe_before, _moe_counters())
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
    want, ref_routing = resolve(ref["forward"])(
        est.variables, _rows(x, n_check), config, with_routing=True)
    want = np.asarray(want, np.float32)
    ref_error = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    ref_error_max = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    agreement = _routing_agreement(
        est, _rows(x, n_check), ref_routing,
        int(config["num_experts_per_tok"]))
    n_steps = len(losses) * steps
    per_layer = n_steps * batch * int(data["seq_len"]) * int(
        config["num_experts_per_tok"])
    bad_epochs = sum(1 for v in losses if not math.isfinite(v))
    checks = {
        "every_epoch_loss_finite": bad_epochs == 0,
        "last_epoch_loss_below_first": (len(losses) > 1
                                        and losses[-1] < losses[0]),
        "reference_within_tolerance": ref_error <= float(ref["tolerance"]),
        "steps_counted_equal_steps_run": counted == n_steps,
        "no_assignment_dropped": all(
            sum(v) == 0 for v in moe["moe_assignments_dropped"].values()),
        "assignments_counted_equal_tokens_times_k": bool(
            moe["moe_assignments"]) and all(
            sum(v) == per_layer for v in moe["moe_assignments"].values()),
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
            "moe": moe,
        },
        "detail": {"checks": checks, "epoch_losses": losses,
                   "epoch_seconds": epoch_s,
                   "samples_per_s_over_whole_window":
                       n_steps * batch / window_s,
                   "reference_error": ref_error,
                   "reference_error_max_norm": ref_error_max,
                   "reference_tolerance": float(ref["tolerance"]),
                   "routing_agreement": agreement,
                   "moe_counters_in_window": moe},
    }
