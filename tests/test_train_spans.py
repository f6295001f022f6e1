"""The train path's spans (ISSUE 35): ``Estimator.fit`` records, always
and with no switch, one ``fit`` span a call and under it ``fit_prepare``,
each step's ``data_wait`` / ``train_step`` (caller's thread) and
``host_batch`` / ``shard_batch`` (producer's thread) with the step's
index ``i``, ``log_sync``, ``epoch_sync`` and ``publish_counters``, all
under one ``trace_id``; one pair of clock readings feeds the span ring
and, with ``profile=True``, the ``TrainingProfiler``; the collector's one
anchor puts a span on CLOCK_REALTIME. The compiled step programs are
pinned elsewhere (tests/test_step_scopes.py)."""

import json
import os
import threading
import time

import flax.linen as nn
import numpy as np
import pytest

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.data.dataset import ZooDataset
from analytics_zoo_tpu.learn.estimator import Estimator
from analytics_zoo_tpu.obs import tracing
from analytics_zoo_tpu.parallel.mesh import default_mesh

BATCH = 8
PER_STEP = ("data_wait", "train_step", "host_batch", "shard_batch")
PER_CALL = ("fit", "fit_prepare", "epoch_sync", "publish_counters")
CALLER = ("fit", "fit_prepare", "data_wait", "train_step", "log_sync",
          "epoch_sync", "publish_counters")


class _Tiny(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(3)(x)


def _data(steps, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(steps * BATCH, 5).astype(np.float32),
            rng.randint(0, 3, steps * BATCH))


def _estimator():
    return Estimator(_Tiny(), loss="sparse_categorical_crossentropy",
                     optimizer="sgd")


def _last_call():
    """The spans of the newest ``fit`` call in the process's ring."""
    tracer = tracing.get_tracer()
    fits = [s for s in tracer.spans() if s["name"] == "fit"]
    return tracer.spans(fits[-1]["trace_id"])


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _fit(steps, epochs=1, est=None, **kwargs):
    est = est or _estimator()
    est.fit(_data(steps), batch_size=BATCH, epochs=est.epoch + epochs,
            **kwargs)
    return est, _last_call()


@pytest.fixture(scope="module")
def one_call():
    """One ``fit`` of 5 steps, profiled: most cases read it."""
    est, spans = _fit(5, profile=True)
    return est, spans


# ------------------------------------------------------------------ #
# what one call records                                              #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("name", PER_CALL)
def test_one_span_a_call(one_call, name):
    assert len(_named(one_call[1], name)) == 1


@pytest.mark.parametrize("name", PER_STEP)
def test_one_span_a_step_with_its_index(one_call, name):
    spans = _named(one_call[1], name)
    assert [s["args"]["i"] for s in spans] == list(range(5))
    threads = {s["thread"] for s in spans}
    assert len(threads) == 1
    on_caller = threads == {threading.current_thread().name}
    assert on_caller == (name in CALLER)


def test_both_threads_share_one_trace_id_and_category(one_call):
    spans = one_call[1]
    assert len({s["trace_id"] for s in spans}) == 1
    assert {s["cat"] for s in spans} == {"train"}
    assert {s["name"] for s in spans} <= set(CALLER) | {"host_batch",
                                                         "shard_batch"}
    producer = {s["thread"] for s in spans if s["name"] not in CALLER}
    assert producer == {"zoo-input-producer"}


def test_fit_span_carries_epoch_and_steps(one_call):
    fit = _named(one_call[1], "fit")[0]
    assert fit["args"] == {"epoch": 1, "steps": 5}
    assert _named(one_call[1], "epoch_sync")[0]["args"] == {"epoch": 1}


def test_children_lie_inside_their_fit(one_call):
    fit = _named(one_call[1], "fit")[0]
    for s in one_call[1]:
        assert fit["t0"] <= s["t0"] <= s["t1"] <= fit["t1"], s["name"]


def test_caller_spans_follow_each_other(one_call):
    """On the caller's thread: ``fit_prepare`` starts with ``fit`` and
    ends before the first ``data_wait``; step ``i`` waits, then
    dispatches; the epoch's sync follows the last step."""
    spans = one_call[1]
    fit, prepare = _named(spans, "fit")[0], _named(spans, "fit_prepare")[0]
    assert prepare["t0"] == fit["t0"]
    at = prepare["t1"]
    for wait, step in zip(_named(spans, "data_wait"),
                          _named(spans, "train_step")):
        assert at <= wait["t0"] <= wait["t1"] <= step["t0"] <= step["t1"]
        at = step["t1"]
    sync, publish = (_named(spans, n)[0]
                     for n in ("epoch_sync", "publish_counters"))
    assert at <= sync["t0"] <= sync["t1"] <= publish["t0"] <= fit["t1"]


def test_a_batch_is_placed_before_its_step_stops_waiting(one_call):
    """The join by ``i``: the producer finished batch ``i`` before the
    caller's ``data_wait`` ``i`` returned."""
    spans = one_call[1]
    placed = {s["args"]["i"]: s for s in _named(spans, "shard_batch")}
    made = {s["args"]["i"]: s for s in _named(spans, "host_batch")}
    for wait in _named(spans, "data_wait"):
        i = wait["args"]["i"]
        assert made[i]["t1"] == placed[i]["t0"]      # one shared reading
        assert placed[i]["t1"] <= wait["t1"]


def test_at_most_ten_spans_a_step(one_call):
    spans = one_call[1]
    per_step = [s for s in spans if "i" in (s.get("args") or {})]
    assert len(per_step) <= 10 * 5
    # the steady state: four a step, and step 0's log sync
    assert len(per_step) == 4 * 5 + 1
    assert _named(spans, "log_sync")[0]["args"] == {"i": 0}


def test_profiler_and_ring_share_one_pair_of_readings(one_call):
    """``profile=True``: the ``Timer``'s totals are the spans' sums, to
    the nanosecond (the same two readings, two sinks), and the summary
    keeps its two stages."""
    est, spans = one_call
    summary = est.last_profile.summary()
    assert set(summary) == {"data_wait", "train_step"}
    for name in ("data_wait", "train_step"):
        stat = est.last_profile.timer.stat(name)
        durations = [s["t1"] - s["t0"] for s in _named(spans, name)]
        assert stat.count == len(durations) == 5
        assert abs(stat.total - sum(durations)) < 1e-9
        assert stat.max == max(durations) and stat.min == min(durations)
    assert 0.0 <= est.last_profile.input_bound_fraction <= 1.0


# ------------------------------------------------------------------ #
# across calls and epochs                                            #
# ------------------------------------------------------------------ #
def test_a_second_call_gets_a_new_id():
    est, first = _fit(2)
    _, second = _fit(2, est=est)
    assert first[0]["trace_id"] != second[0]["trace_id"]
    assert [s["args"]["i"] for s in _named(second, "train_step")] == [0, 1]
    # no profile asked for: spans all the same, no profiler
    assert est.last_profile is None and len(_named(second, "fit")) == 1


@pytest.mark.parametrize("name", PER_STEP)
def test_the_index_runs_on_across_the_epochs_of_a_call(name):
    _, spans = _fit(3, epochs=2)
    assert [s["args"]["i"] for s in _named(spans, name)] == list(range(6))
    assert [s["args"]["epoch"] for s in _named(spans, "epoch_sync")] == [1, 2]
    assert len(_named(spans, "fit_prepare")) == 1
    assert _named(spans, "fit")[0]["args"] == {"epoch": 2, "steps": 6}


def test_log_sync_follows_the_logging_cadence():
    cfg = get_config()
    cfg.set("zoo.train.log_every_n_steps", 2)
    try:
        _, spans = _fit(5)
    finally:
        cfg.unset("zoo.train.log_every_n_steps")
    # global_step 1 (always), 2 and 4
    assert [s["args"]["i"] for s in _named(spans, "log_sync")] == [0, 1, 3]


def test_validation_records_no_producer_span():
    """``evaluate`` inside ``fit`` runs its own iterator: it passes no
    ``spans``, so no second batch claims a train step's ``i``."""
    est = _estimator()
    est.fit(_data(3), batch_size=BATCH, epochs=1,
            validation_data=_data(2, seed=1))
    spans = _last_call()
    for name in ("host_batch", "shard_batch"):
        assert [s["args"]["i"] for s in _named(spans, name)] == [0, 1, 2]


def test_an_exception_inside_fit_still_closes_fit():
    est = _estimator()
    x, y = _data(3)

    class Boom(RuntimeError):
        pass

    def broken(*a, **k):
        raise Boom()

    est.fit((x, y), batch_size=BATCH, epochs=1)
    est._train_step = broken        # the built step, replaced
    with pytest.raises(Boom):
        est.fit((x, y), batch_size=BATCH, epochs=2)
    spans = _last_call()
    assert len(_named(spans, "fit")) == 1
    assert len(_named(spans, "train_step")) == 1     # closed by the raise
    assert _named(spans, "epoch_sync") == []
    fit = _named(spans, "fit")[0]
    # (the producer may still be placing the batches it had queued)
    assert all(fit["t0"] <= s["t0"] <= s["t1"] <= fit["t1"] for s in spans
               if s["name"] in CALLER)


def test_device_cached_fit_keeps_its_one_stage():
    est = _estimator()
    est.fit(_data(4), batch_size=BATCH, epochs=2, device_cache=True)
    spans = _last_call()
    assert sorted({s["name"] for s in spans}) == ["fit", "train_step"]
    assert len(_named(spans, "train_step")) == 2
    assert _named(spans, "fit")[0]["args"] == {"epoch": 2, "steps": 8}


def test_iterator_without_spans_records_nothing():
    tracer = tracing.get_tracer()
    before = len([s for s in tracer.spans() if s["name"] == "host_batch"])
    x, y = _data(2)
    ds = ZooDataset.from_ndarrays(x, y)
    assert len(list(ds.device_iterator(BATCH, mesh=default_mesh()))) == 2
    after = len([s for s in tracer.spans() if s["name"] == "host_batch"])
    assert after == before
    got = list(ds.device_iterator(BATCH, mesh=default_mesh(),
                                  spans=("tid-iter", 7)))
    assert len(got) == 2
    assert [(s["name"], s["args"]["i"]) for s in tracer.spans("tid-iter")] \
        == [("host_batch", 7), ("shard_batch", 7),
            ("host_batch", 8), ("shard_batch", 8)]


# ------------------------------------------------------------------ #
# the clock, the export, the budget                                  #
# ------------------------------------------------------------------ #
def test_the_anchor_puts_a_span_on_the_wall_clock():
    """A span taken now maps to within 1 ms of ``time.time_ns()`` (the
    best of a few tries: a loaded host can hold the thread between the
    two clocks' readings)."""
    tracer = tracing.Tracer(max_spans=8)
    errors = []
    for _ in range(5):
        t0 = time.perf_counter()
        wall = time.time_ns()
        t1 = time.perf_counter()
        start, end = tracer.wall_ns({"t0": t0, "t1": t1})
        assert end - start == pytest.approx((t1 - t0) * 1e9, abs=2)
        errors.append(max(abs(start - wall), abs(end - wall)))
    assert min(errors) < 1_000_000
    # the process's collector has the same method
    assert tracing.get_tracer().wall_ns({"t0": t0, "t1": t1})[0] == \
        pytest.approx(wall, abs=5e7)


@pytest.mark.parametrize("cat, want", [(None, "serving"), ("train", "train")])
def test_chrome_trace_takes_its_category_from_the_span(cat, want):
    t = tracing.Tracer(max_spans=4)
    t.add_span("a", "t1", 1.0, 1.5, cat=cat, i=3)
    event = t.chrome_trace()["traceEvents"][0]
    assert event["cat"] == want and event["args"] == {"i": 3,
                                                       "trace_id": "t1"}
    assert event["ts"] == t.wall_ns(t.spans()[0])[0] / 1e3
    assert "cat" not in t.spans()[0] or cat is not None


def test_dropped_counts_what_fell_off_the_ring():
    """0 means the ring still holds the process's first span (what the
    benchmark's set-up readers ask before they read it)."""
    t = tracing.Tracer(max_spans=3)
    for i in range(3):
        t.add_span("s", "t", 0.0, 1.0, i=i)
    assert t.dropped == 0
    t.add_span("s", "t", 0.0, 1.0, i=3)
    t.add_span("s", "t", 0.0, 1.0, i=4)
    assert t.dropped == 2 and [s["args"]["i"] for s in t.spans()] == [2, 3, 4]
    t.clear()
    assert t.dropped == 5 and t.spans() == []


def test_trace_dir_gets_the_calls_spans_on_the_wall_clock(tmp_path):
    before = time.time_ns()
    _, spans = _fit(2, trace_dir=str(tmp_path))
    after = time.time_ns()
    tid = spans[0]["trace_id"]
    path = tmp_path / f"fit_spans.{tid}.trace.json"
    with open(path) as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    assert sorted(e["name"] for e in events) == sorted(
        s["name"] for s in spans)
    assert all(e["cat"] == "train" and e["args"]["trace_id"] == tid
               for e in events)
    assert all(before / 1e3 <= e["ts"] <= after / 1e3 for e in events)
    # beside the device trace
    assert os.path.isdir(tmp_path / "plugins" / "profile")


def test_a_steps_spans_cost_under_the_budget():
    """ROADMAP S1b's budget: at most 20 us a step for the spans. Four
    ``add_span`` and their clock readings, timed here as the best of
    several rounds (a loaded host stretches the others)."""
    t = tracing.Tracer(max_spans=1024)
    best = float("inf")
    for _ in range(20):
        start = time.perf_counter()
        for i in range(200):
            for name in PER_STEP:
                t0 = time.perf_counter()
                t.add_span(name, "tid", t0, time.perf_counter(),
                           cat="train", i=i)
        best = min(best, (time.perf_counter() - start) / 200)
    assert best < 20e-6


def test_no_new_config_key_or_fit_argument():
    import inspect

    from analytics_zoo_tpu.common import config

    assert len(config._DEFAULTS) == 101
    assert list(inspect.signature(Estimator.fit).parameters) == [
        "self", "data", "batch_size", "epochs", "validation_data",
        "validation_trigger", "checkpoint_dir", "checkpoint_trigger",
        "log_dir", "resume", "device_cache", "profile", "trace_dir"]
