"""Device idle by cause (benchmark/lib/host_spans.py): the attribution on
hand-made device lists and spans, one case per cause, the clock check,
the sum rule, the device choice on a four-device trace, one epoch
recorded on the chip, and the seven readers over it."""

import importlib
import json
import os

import pytest

from benchmark.lib import host_spans as hs
from benchmark.lib import trace_reduce as tr

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("device_idle_attributed_share", "train_input_exposed_share",
           "train_host_late_ms", "train_fit_call_idle_ms",
           "train_host_step_ms")
SETUP_READERS = ("setup_first_step_s", "setup_fit_prepare_s")


def _reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


# Three steps of 100 us on one device, in ns. The caller's thread by
# default: prepare 0-20 us, wait 0 20-30, dispatch 0 32-40 (run 0 starts
# at 45), then waits and dispatches well ahead of the device; the epoch's
# sync ends 5 us after the last operation, the call returns at 400 us.
RUNS = [(45e3, 145e3), (150e3, 250e3), (255e3, 355e3)]
SPANS = [
    ("fit", 0.0, 400e3, None),
    ("fit_prepare", 0.0, 20e3, None),
    ("data_wait", 20e3, 30e3, 0), ("train_step", 32e3, 40e3, 0),
    ("data_wait", 41e3, 42e3, 1), ("train_step", 43e3, 50e3, 1),
    ("data_wait", 51e3, 52e3, 2), ("train_step", 53e3, 60e3, 2),
    ("epoch_sync", 61e3, 360e3, None),
    ("publish_counters", 362e3, 370e3, None),
    ("host_batch", 5e3, 12e3, 0), ("shard_batch", 12e3, 29e3, 0),
    ("host_batch", 29e3, 33e3, 1), ("shard_batch", 33e3, 41e3, 1),
    ("host_batch", 41e3, 44e3, 2), ("shard_batch", 44e3, 50e3, 2),
]
WINDOW = 402e3      # the runner's clock round model.fit: 2 us of wrapper


def _replace(spans, name, i, start, end):
    return [(name, start, end, i) if (s[0], s[3]) == (name, i) else s
            for s in spans]


def _attribute(runs=RUNS, busy=None, spans=SPANS, window=WINDOW):
    return hs.attribute(runs, list(runs) if busy is None else busy, spans,
                        window)


def _nonzero(result):
    return {c: ns for c, ns in result["causes"].items() if ns}


def test_queued_programs_and_the_calls_two_edges():
    """The default case: every later step was dispatched before the one
    ahead of it ended, so the 5 us between runs are the runtime's; the
    edges go to what the caller was in."""
    r = _attribute()
    assert _nonzero(r) == {
        "fit_prepare": 20e3, "first_batch": 10e3,
        # the loop's 2 us, the dispatch's 8 and the launch's 5
        "train_step_0": 2e3 + 8e3 + 5e3,
        "fit_loop": 2e3,                       # sync -> publish
        "device_queue": 10e3,
        "epoch_sync": 5e3, "publish_counters": 8e3, "fit_return": 30e3,
        "no_span": 2e3}
    assert r["steps"] == 3 and r["idle_ns"] == WINDOW - 300e3
    assert r["waits"] == {0: 10e3}
    assert r["slacks"] == (13e3, 5e3)


def test_a_late_batch_is_the_input_paths_with_its_producer_named(capsys):
    """Batch 2 arrives late: the caller sits in ``data_wait`` 2 until
    270 us and dispatches by 280; run 2 starts at 285."""
    runs = RUNS[:2] + [(285e3, 385e3)]
    spans = _replace(SPANS, "data_wait", 2, 51e3, 270e3)
    spans = _replace(spans, "train_step", 2, 272e3, 280e3)
    spans = _replace(spans, "epoch_sync", None, 281e3, 390e3)
    spans = _replace(spans, "publish_counters", None, 391e3, 395e3)
    spans = _replace(spans, "host_batch", 2, 41e3, 200e3)
    spans = _replace(spans, "shard_batch", 2, 200e3, 269e3)
    r = _attribute(runs, spans=spans)
    got = _nonzero(r)
    assert got["data_wait"] == 20e3            # 250 -> 270
    assert got["fit_loop"] == 2e3 + 1e3        # 270 -> 272; sync -> publish
    assert got["train_step"] == 8e3            # 272 -> 280
    assert got["launch"] == 5e3                # 280 -> 285
    assert got["train_step_0"] == 15e3
    assert got["device_queue"] == 5e3          # run 0 -> run 1 only
    assert r["waits"] == {0: 10e3, 2: 20e3}
    hs.report(r, spans)
    err = capsys.readouterr().err
    assert ("data_wait 2: device idle 0.020 ms; the producer's host_batch 2 "
            "took 0.159 ms, shard_batch 2 0.069 ms") in err
    assert "data_wait" in err and "ms a step" in err


def test_a_log_sync_holds_the_next_dispatch():
    """After step 0 the caller syncs on its loss (``log_sync`` until run
    0 ends at 145 + 2), so step 1 is dispatched only then."""
    runs = [RUNS[0], (165e3, 265e3), (270e3, 370e3)]
    spans = SPANS[:4] + [
        ("log_sync", 40.5e3, 147e3, 0),
        ("data_wait", 148e3, 149e3, 1), ("train_step", 150e3, 160e3, 1),
        ("data_wait", 161e3, 162e3, 2), ("train_step", 163e3, 170e3, 2),
        ("epoch_sync", 171e3, 375e3, None),
        ("publish_counters", 376e3, 380e3, None)]
    got = _nonzero(_attribute(runs, spans=spans))
    assert got["log_sync"] == 2e3              # 145 -> 147
    assert got["data_wait"] == 1e3 and got["train_step"] == 10e3
    assert got["fit_loop"] == 1e3 + 1e3 + 1e3  # 147-148, 149-150, 375-376
    assert got["launch"] == 5e3                # 160 -> 165
    assert got["device_queue"] == 5e3          # run 1 -> run 2: queued


def test_launch_is_what_follows_the_dispatchs_return():
    """Step 1's dispatch returns at 147, 2 us into the gap; run 1 starts
    at 160: 2 us the dispatch's, 13 the launch's. A program whose
    dispatch returned before the gap began would be ``device_queue``."""
    runs = [RUNS[0], (160e3, 260e3), (265e3, 365e3)]
    spans = _replace(SPANS, "train_step", 1, 43e3, 147e3)
    spans = _replace(spans, "data_wait", 2, 148e3, 149e3)
    spans = _replace(spans, "train_step", 2, 150e3, 155e3)
    spans = _replace(spans, "epoch_sync", None, 156e3, 370e3)
    spans = _replace(spans, "publish_counters", None, 371e3, 375e3)
    got = _nonzero(_attribute(runs, spans=spans))
    assert got["train_step"] == 2e3
    assert got["launch"] == 13e3
    assert got["device_queue"] == 5e3


def test_a_gap_inside_a_program_run_is_inside_step():
    busy = [RUNS[0], (150e3, 180e3), (187e3, 250e3), RUNS[2]]
    r = _attribute(busy=busy)
    assert r["causes"]["inside_step"] == 7e3
    assert r["idle_ns"] == WINDOW - 293e3
    # an idle stretch that begins before a run and ends inside it is cut
    # at the run's start
    busy = [RUNS[0], (153e3, 250e3), RUNS[2]]
    r = _attribute(busy=busy)
    assert r["causes"]["inside_step"] == 3e3
    assert r["causes"]["device_queue"] == 10e3


def test_other_programs_between_the_steps_are_busy_time():
    """The key's split runs between the steps: a blip of busy time in
    the gap, and the rule still reads the whole gap's beginning."""
    busy = [RUNS[0], (146e3, 147e3), RUNS[1], RUNS[2]]
    r = _attribute(busy=busy)
    assert r["causes"]["device_queue"] == 9e3


CASES = {
    "queued": {},
    "inside": {"busy": [RUNS[0], (150e3, 180e3), (187e3, 250e3), RUNS[2]]},
    "long_window": {"window": 500e3},
    "odd_numbers": {
        "runs": [(45123.25, 145001.5), (150777.125, 250000.75),
                 (255003.5, 355999.875)]},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_causes_add_up_to_the_idle_time(case):
    r = _attribute(**CASES[case])
    busy = CASES[case].get("busy", CASES[case].get("runs", RUNS))
    assert r["idle_ns"] == r["window_ns"] - tr.measure(busy)
    assert sum(r["causes"].values()) == pytest.approx(r["idle_ns"], abs=1.0)
    assert set(r["causes"]) == set(hs.CAUSES)
    assert all(ns >= 0 for ns in r["causes"].values())
    groups = hs.INPUT + hs.HOST_LATE + hs.CALL_EDGES + (
        hs.QUEUE, hs.INSIDE, hs.NO_SPAN)
    assert sorted(groups) == sorted(hs.CAUSES)       # each cause once


def test_the_hosts_step_leaves_out_the_steps_the_runtime_held_back():
    """Six steps of 100 us on the device; the caller needs 10 us a step
    (4 after the wait) until two programs are queued, then each dispatch
    blocks until a run ends. A log sync is not the host's cost either."""
    runs = [(50e3 + 100e3 * k, 150e3 + 100e3 * k) for k in range(6)]
    spans = [("fit", 0.0, 700e3, None)]
    at = 0.0
    for i in range(6):
        blocked = max(at + 6e3, runs[i - 2][1] if i >= 2 else 0.0)
        spans += [("data_wait", at, at + 6e3, i),
                  ("train_step", blocked + 1e3, blocked + 4e3, i)]
        at = blocked + 4e3
        if i == 0:
            spans.append(("log_sync", at, at + 50e3, 0))
            at += 50e3
    spans.append(("epoch_sync", at, 655e3, None))
    # steps 0-2 cost 4 us (step 0's sync taken out); 3-5 wait for a run
    assert hs.host_step_ms(spans, runs) == pytest.approx(4e-3)
    # no run to compare with: every step counts
    assert hs.host_step_ms(spans, []) > 4e-3
    assert hs.host_step_ms([], runs) is None


# ------------------------------------------------------------------ #
# the clock                                                          #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("shift_us, attributed", [
    (0, True), (-900, True), (-1100, False), (1100, False)])
def test_a_clock_off_by_more_than_a_millisecond_attributes_nothing(
        shift_us, attributed, capsys):
    """The spans' clock shifted against the trace's: run k before its
    own dispatch (spans late) or the device at work after the epoch's
    sync returned (spans early)."""
    long_runs = [(s + 2e6, e + 2e6) for s, e in RUNS]
    spans = [(n, s + 2e6 * (n not in ("fit", "fit_prepare")) + shift_us * 1e3
              * (n != "fit"), e + 2e6 * (n != "fit_prepare")
              + shift_us * 1e3 * (n != "fit"), i)
             for n, s, e, i in SPANS]
    spans[0] = ("fit", -2e6, 6e6, None)
    r = hs.attribute(long_runs, long_runs, spans, 8.1e6)
    err = capsys.readouterr().err
    assert "clock check" in err
    if attributed:
        assert r is not None and min(r["slacks"]) >= -1e6
    else:
        assert r is None and "disagree by more than 1 ms" in err


def test_runs_that_do_not_pair_with_the_spans_attribute_nothing(capsys):
    assert _attribute(RUNS[:2], busy=RUNS) is None
    assert "do not pair" in capsys.readouterr().err
    assert hs.attribute(RUNS, RUNS, SPANS[1:], WINDOW) is None


# ------------------------------------------------------------------ #
# the device's side                                                  #
# ------------------------------------------------------------------ #
def _events(ops_by_device):
    return {"devices": {
        key: {"ops": [[name, s, e - s] for name, s, e in ops], "async": [],
              "modules": [["jit_step", 45e3 + 105e3 * k, 100e3]
                          for k in range(3)] + [["jit_split", 146e3, 1e3]]}
        for key, ops in ops_by_device.items()}, "host": []}


def test_the_least_busy_device_is_device_idle_shares():
    """Four chips: the device ``trace_reduce`` calls the worst, by the
    same operations (a ``while`` wrapper is no work of its own) and, on
    a tie, the same order."""
    ops = [("fusion.1", s, e) for s, e in RUNS]
    events = _events({
        "0": ops + [("while.2", 0.0, 400e3)],
        "3": [("fusion.1", s, e - 4e3) for s, e in RUNS],
        "2": [("fusion.1", s, e - 4e3) for s, e in RUNS],
        "1": [("all-reduce.1", s + 1e3, e) for s, e in RUNS]})
    key, busy = hs.least_busy(events)
    reduced = tr.reduce_trace(events, window_s=WINDOW / 1e9)
    assert key == "2"
    assert tr.measure(busy) / 1e9 == min(reduced["busy_s_by_device"])
    runs = hs.step_runs(events["devices"][key]["modules"])
    assert runs == RUNS
    r = hs.attribute(runs, busy, SPANS, WINDOW)
    assert r["idle_ns"] / r["window_ns"] == pytest.approx(
        reduced["idle_share_worst"], rel=1e-12)
    assert r["causes"]["inside_step"] == 12e3     # each run's idle tail
    assert r["causes"]["device_queue"] == 10e3


# ------------------------------------------------------------------ #
# one epoch recorded on the chip                                     #
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(
            DATA, "recorded_v5e_1chip_resnet_epoch_spans.json")) as f:
        data = json.load(f)
    lo, hi = data["extent"]
    busy = tr.gaps([tuple(g) for g in data["gaps"]], lo, hi)
    data["runs"] = [tuple(r) for r in data["runs"]]
    data["spans"] = [tuple(s) for s in data["spans"]]
    # the gaps under 500 ns, inside program runs, are busy time here:
    # the idle time and ``inside_step`` read short by their total
    data["result"] = hs.attribute(data["runs"], busy, data["spans"],
                                  data["window_ns"])
    return data


def test_recorded_resnet_epoch(recorded):
    """The ResNet-50 cell's traced epoch (my chip run, PR 35, seed
    2147530101): 64 steps of 98.8 ms, 38.5 MB a batch from host arrays.
    Pinned to what that run's table printed (``printed`` in the file)."""
    r, short = recorded["result"], recorded["short_gaps"]["total_ns"]
    printed = recorded["printed"]
    assert r["steps"] == 64 and short == 278978.0
    assert printed["idle_ns"] == pytest.approx(106.641130e6, abs=1.0)
    assert r["idle_ns"] + short == pytest.approx(printed["idle_ns"], abs=1.0)
    assert list(r["slacks"]) == printed["slacks"] == [-198787.0, 2368575.0]
    for cause, ns in printed["causes"].items():
        here = r["causes"][cause] + (short if cause == "inside_step" else 0)
        # (a few of the gaps left out lie round the small programs
        # between the steps: under a microsecond moves between causes)
        assert here == pytest.approx(ns, abs=1000.0), cause
    assert {c: round(ns / 1e6, 3) for c, ns in printed["causes"].items()} == {
        "fit_prepare": 4.024, "first_batch": 46.412, "train_step_0": 38.489,
        "data_wait": 0.593, "train_step": 0.057, "log_sync": 4.847,
        "fit_loop": 7.158, "launch": 0.0, "device_queue": 1.18,
        "inside_step": 0.816, "epoch_sync": 2.367,
        "publish_counters": 0.002, "fit_return": 0.665, "no_span": 0.029}
    assert sum(r["causes"].values()) == pytest.approx(r["idle_ns"], abs=1.0)
    # step 0 unfolded: the loop's Python (the key's split), the dispatch,
    # and 32.8 ms in which the dispatched program waited for its batch
    assert {c: round(ns / 1e6, 3) for c, ns in r["before_first"].items()} \
        == {"fit_prepare": 4.024, "first_batch": 46.412, "fit_loop": 2.68,
            "train_step_0": 3.034, "launch": 32.774}
    # the first batch's wait, and two waits that log syncs exposed
    assert {i: round(ns / 1e6, 3) for i, ns in r["waits"].items()} == {
        0: 46.412, 6: 0.077, 56: 0.516}
    # 61 of the 63 gaps between runs: the next program was queued
    assert r["causes"]["device_queue"] / 61 == pytest.approx(19.3e3, rel=0.01)


def test_recorded_epochs_host_step_leaves_the_held_steps_out(recorded):
    """The caller's step costs 5.7 ms (the key's split 2.7, the dispatch
    3.0); over all 64 steps the median reads the device's 98 ms, because
    from step 10 on the runtime holds the caller at five queued runs."""
    spans, runs = recorded["spans"], recorded["runs"]
    assert hs.host_step_ms(spans, runs) == pytest.approx(
        recorded["printed"]["host_step_ms"], abs=1e-9)
    assert hs.host_step_ms(spans, runs) == pytest.approx(5.734385, abs=1e-6)
    assert hs.host_step_ms(spans, []) == pytest.approx(98.0, abs=0.5)


def test_recorded_epochs_producer_is_named_beside_its_wait(recorded, capsys):
    hs.report(recorded["result"], recorded["spans"])
    err = capsys.readouterr().err
    assert ("data_wait 0: device idle 46.412 ms; the producer's "
            "host_batch 0 took 43.310 ms, shard_batch 0 2.051 ms") in err
    assert "traced fit call of 64 steps" in err
    assert "before run 0, unfolded: fit_prepare 4.024" in err


# ------------------------------------------------------------------ #
# the readers                                                        #
# ------------------------------------------------------------------ #
@pytest.fixture()
def attributed(monkeypatch):
    result = _attribute()
    result["host_step_ms"] = hs.host_step_ms(SPANS, RUNS)
    monkeypatch.setattr(hs, "for_cell", lambda ctx: result)
    return result


def test_readers_over_one_attribution(attributed):
    ctx = {"trace": {"window_s": WINDOW / 1e9}}
    idle = attributed["idle_ns"]
    assert _reader("device_idle_attributed_share").read(ctx) == \
        pytest.approx(100 * (1 - 2e3 / idle))
    assert _reader("train_input_exposed_share").read(ctx) == \
        pytest.approx(100 * 10e3 / WINDOW)
    assert _reader("train_host_late_ms").read(ctx) == \
        pytest.approx(2e3 / 3 / 1e6)
    assert _reader("train_fit_call_idle_ms").read(ctx) == \
        pytest.approx((20e3 + 15e3 + 5e3 + 8e3 + 30e3) / 1e6)
    # the caller per step outside data_wait: 11, 9 and 9 us, dispatched
    # at a depth of 1, 2 and 3 programs: the deepest is left out
    assert _reader("train_host_step_ms").read(ctx) == pytest.approx(10e-3)
    # the four in ns and the table's three add up to the idle time
    c = attributed["causes"]
    total = (sum(c[k] for k in hs.INPUT + hs.HOST_LATE + hs.CALL_EDGES)
             + c[hs.QUEUE] + c[hs.INSIDE] + c[hs.NO_SPAN])
    assert total == pytest.approx(idle, abs=1.0)


@pytest.mark.parametrize("name", READERS)
def test_every_reader_reads_none_where_nothing_was_attributed(
        name, monkeypatch):
    """A failed clock check, a CPU rehearsal (no device trace), a tree
    whose program records no span: ``None``, never a guess."""
    assert _reader(name).read({"trace": None}) is None
    monkeypatch.setattr(hs, "for_cell", lambda ctx: None)
    assert _reader(name).read({"trace": {"window_s": 1.0}}) is None


def test_for_cell_without_a_trace_file(tmp_path, monkeypatch):
    ctx = {"trace": {"window_s": 1.0}, "cell": {"name": "no-such.cell"}}
    assert hs.for_cell(ctx) is None


@pytest.mark.parametrize("name, span", zip(SETUP_READERS,
                                           ("train_step", "fit_prepare")))
def test_setup_readers_read_the_processs_first_span(name, span, monkeypatch):
    from analytics_zoo_tpu.obs import tracing

    tracer = tracing.Tracer(max_spans=4)
    monkeypatch.setattr(tracing, "get_tracer", lambda: tracer)
    assert _reader(name).read({}) is None             # no span yet
    tracer.add_span(span, "t1", 1.0, 3.5, cat="train", i=0)
    tracer.add_span(span, "t2", 5.0, 5.25, cat="train", i=0)
    assert _reader(name).read({}) == 2.5              # the first, not the last
    for k in range(3):
        tracer.add_span("data_wait", "t2", 6.0, 6.1, cat="train", i=k)
    assert tracer.dropped == 1
    assert _reader(name).read({}) is None             # the ring has wrapped


def test_a_program_without_train_spans_gives_nothing(monkeypatch):
    """The parent's tree under this PR's benchmark files: its collector
    has no ``wall_ns`` and no ``dropped``, its ring no ``fit`` span."""
    from analytics_zoo_tpu.obs import tracing

    class Old:
        def spans(self):
            return [{"name": "decode", "trace_id": "t", "t0": 0.0, "t1": 1.0}]

    monkeypatch.setattr(tracing, "get_tracer", lambda: Old())
    assert hs.last_call_spans(0) is None
    assert hs.first_span_s("train_step") is None
    tracer = tracing.Tracer(max_spans=4)
    monkeypatch.setattr(tracing, "get_tracer", lambda: tracer)
    assert hs.last_call_spans(0) is None              # no fit span
    tracer.add_span("fit", "t9", 2.0, 3.0, cat="train")
    tracer.add_span("train_step", "t9", 2.25, 2.5, cat="train", i=0)
    zero = tracer.wall_ns({"t0": 2.0, "t1": 2.0})[0]
    assert hs.last_call_spans(zero) == [
        ("fit", 0.0, 1e9, None), ("train_step", 0.25e9, 0.5e9, 0)]


# ------------------------------------------------------------------ #
# BENCHMARK.json                                                     #
# ------------------------------------------------------------------ #
def test_the_seven_entries_are_appended_for_all_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    tail = bench["per_layer"][-7:]
    assert [m["name"] for m in tail] == list(READERS + SETUP_READERS)
    assert all("workloads" not in m and m["source"] == "program_span"
               for m in tail)
    assert [m["layer"] for m in tail] == [
        "device", "input path"] + ["entry points"] * 5
    assert [m["moves"] for m in tail] == (["train_samples_per_s"] * 5
                                          + ["setup_s"] * 2)
    assert [m["better"] for m in tail] == ["higher"] + ["lower"] * 6
    layers = {m["layer"] for m in bench["per_layer"][:-7]}
    assert {m["layer"] for m in tail} <= layers       # no new layer name
