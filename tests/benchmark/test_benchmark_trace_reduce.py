"""The trace reduction (benchmark/lib/trace_reduce.py) on a small trace
recorded on the chip and on hand-made event lists."""

import json
import os

import pytest

from benchmark.lib import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _recorded(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _sampled_cover(intervals, lo, hi, step):
    """Independent of the interval code: count the grid points covered."""
    n = 0
    t = lo + step / 2
    while t < hi:
        n += any(s <= t < e for s, e in intervals)
        t += step
    return n * step


def test_recorded_one_chip_trace():
    """Six steps of a toy MLP traced on a TPU v5 lite (chip probe, PR
    22): 108 device operations, host spans ``bench_window``/``my_step``."""
    events = _recorded("recorded_v5e_1chip_toy_mlp.json")
    r = tr.reduce_trace(events)
    assert r["devices"] == 1
    assert r["module_runs"] == {"jit_step": 6}
    # the window is the outer host span: it starts before the first op
    # and ends after the last
    (name, start, dur), = [e for e in events["host"]
                           if e[0] == "bench_window"]
    assert r["window_s"] == pytest.approx(dur / 1e9, rel=1e-9)
    ops = [(s, s + d) for _, s, d in events["devices"]["0"]["ops"]]
    want_busy = _sampled_cover(ops, start, start + dur, 50.0) / 1e9
    assert r["busy_s"] == pytest.approx(want_busy, rel=2e-3)
    assert r["busy_s"] == pytest.approx(0.005416392, rel=1e-6)
    assert r["idle_share_worst"] == pytest.approx(
        1 - r["busy_s"] / r["window_s"])
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
    # the five fusions of the step carry all the time, ~1.07-1.11 ms each
    # over six steps
    top = dict(r["ops"][:5])
    assert set(top) == {"fusion.2", "fusion.3", "convolution_tanh_fusion.1",
                        "multiply_add_fusion.1",
                        "convolution_multiply_fusion"}
    assert sum(top.values()) == pytest.approx(r["busy_s"], rel=1e-3)
    # gaps: longest first, labelled by the innermost host span at their
    # middle; between steps the host sat in bench_window (a 2 ms sleep)
    gaps = r["idle_gaps"]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert gaps[0][0] == "bench_window" and gaps[0][1] > 2e-3
    assert sum(g[1] for g in gaps) <= r["window_s"] - r["busy_s"] + 1e-12


@pytest.mark.parametrize("intervals,merged", [
    ([], []),
    ([(0, 10)], [(0, 10)]),
    ([(5, 7), (0, 10)], [(0, 10)]),                       # nested
    ([(0, 4), (3, 8), (8, 9)], [(0, 9)]),                 # chained, touching
    ([(0, 2), (5, 6), (1, 3)], [(0, 3), (5, 6)]),         # unsorted
    ([(4, 4), (6, 5)], []),                               # empty / reversed
])
def test_union(intervals, merged):
    assert tr.union(intervals) == merged
    assert tr.measure(tr.union(intervals)) == sum(e - s for s, e in merged)


def test_gaps_clip_subtract():
    merged = [(2, 4), (6, 9)]
    assert tr.gaps(merged, 0, 10) == [(0, 2), (4, 6), (9, 10)]
    assert tr.gaps(merged, 3, 7) == [(4, 6)]
    assert tr.gaps([], 1, 5) == [(1, 5)]
    assert tr.clip(merged, 3, 7) == [(3, 4), (6, 7)]
    assert tr.subtract([(0, 10)], merged) == [(0, 2), (4, 6), (9, 10)]
    assert tr.subtract(merged, [(0, 10)]) == []


def _dev(ops, async_ops=(), modules=()):
    return {"ops": [list(e) for e in ops],
            "async": [list(e) for e in async_ops],
            "modules": [list(e) for e in modules]}


@pytest.mark.parametrize("events", [
    {},                                              # no planes at all
    {"devices": {}, "host": [["fit", 0, 100]]},      # host only
    {"devices": {"0": _dev([])}, "host": []},        # an empty device plane
])
def test_nothing_on_the_device_reads_as_nothing(events):
    assert tr.reduce_trace(events) is None


def test_overlapping_ops_count_once():
    events = {"devices": {"0": _dev([("fusion.1", 0, 60), ("copy.1", 40, 40),
                                     ("fusion.2", 90, 10)])}}
    r = tr.reduce_trace(events)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(90e-9)       # [0,80] + [90,100]
    assert r["idle_share_worst"] == pytest.approx(0.10)
    assert r["idle_gaps"] == [["no_span", pytest.approx(10e-9)]]


def test_collectives_sync_async_and_exposed_part():
    """Device 0: a synchronous all-reduce (nothing can hide it) and an
    asynchronous one whose span [200, 300] is covered by compute on
    [220, 280]. Device 1: same, but fully hidden. ``while`` wraps all."""
    def plane(hidden_from, hidden_to):
        return _dev(
            ops=[("while.1", 0, 400), ("fusion.1", 0, 100),
                 ("all-reduce.1", 100, 50),
                 ("all-reduce-start.2", 200, 1),
                 ("fusion.2", hidden_from, hidden_to - hidden_from),
                 ("all-reduce-done.2", 299, 1), ("fusion.3", 300, 100)],
            async_ops=[("all-reduce-start.2", 200, 100)])

    events = {"devices": {"0": plane(220, 280), "1": plane(200, 300)},
              "host": [["fit_epoch", 0, 400]]}
    r = tr.reduce_trace(events)
    assert r["devices"] == 2
    assert r["collective_s"] == pytest.approx(150e-9)
    # device 0: 50 sync + (100 - 60 hidden) = 90; device 1: 50 + 0
    assert r["collective_exposed_s"] == pytest.approx((90 + 50) / 2 * 1e-9)
    # the wrapper is neither busy time nor an operation of the table
    assert "while.1" not in dict(r["ops"])
    # device 0 idles on [150,200] and between start and hidden compute
    assert r["busy_s_by_device"][0] == pytest.approx(312e-9)
    assert r["busy_s_by_device"][1] == pytest.approx(350e-9)
    assert r["idle_share_worst"] == pytest.approx(1 - 312 / 400)
    assert r["idle_gaps"][0] == ["fit_epoch", pytest.approx(50e-9)]


def test_gap_label_prefers_the_innermost_span():
    events = {"devices": {"0": _dev([("f", 0, 10), ("f", 50, 10)])},
              "host": [["fit_epoch", 0, 60], ["data_wait", 20, 25]]}
    assert tr.reduce_trace(events)["idle_gaps"] == [
        ["data_wait", pytest.approx(40e-9)]]


@pytest.mark.parametrize("text,name", [
    ("%fusion.12 = bf16[8,128]{1,0} fusion(bf16[8]{0} %p), kind=kLoop",
     "fusion.12"),
    ("%all-reduce-start.3 = (f32[4]) all-reduce-start(f32[4] %x)",
     "all-reduce-start.3"),
    ("fusion.7", "fusion.7"),
])
def test_op_name(text, name):
    assert tr.op_name(text) == name


def test_host_clock_window_longer_than_the_device_extent():
    """No host span in the trace: the window's length comes from the
    host's clock, and what lies outside the device's extent is idle."""
    events = {"devices": {"0": _dev([("f", 0, 40), ("f", 60, 40)])}}
    r = tr.reduce_trace(events, window_s=150e-9)
    assert r["window_s"] == pytest.approx(150e-9)
    assert r["busy_s"] == pytest.approx(80e-9)
    assert r["idle_share_worst"] == pytest.approx(1 - 80 / 150)
    assert r["idle_gaps"] == [[tr.OUTSIDE, pytest.approx(50e-9)],
                              ["no_span", pytest.approx(20e-9)]]
    # a host clock that reads shorter than the device's extent adds nothing
    r = tr.reduce_trace(events, window_s=90e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert [g[0] for g in r["idle_gaps"]] == ["no_span"]


def test_recorded_four_chip_trace_collectives():
    """The last 12 ms of one BERT-base dp4 step and the start of the
    next, chips 0 and 1 of the four (chip run, PR 22): the two large
    gradient all-reduces run here, synchronously, so nothing hides them."""
    events = _recorded("recorded_v5e_4chip_bert_step_tail.json")
    r = tr.reduce_trace(events)
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(0.013932026, rel=1e-9)
    assert r["busy_s_by_device"] == [pytest.approx(0.012256436, rel=1e-9),
                                     pytest.approx(0.012255571, rel=1e-9)]
    table = dict(r["ops"])
    assert table["all-reduce.152"] == pytest.approx(2.147e-3, rel=1e-3)
    assert table["all-reduce.151"] == pytest.approx(1.676e-3, rel=1e-3)
    assert r["collective_s"] == pytest.approx(
        table["all-reduce.152"] + table["all-reduce.151"], rel=1e-6)
    assert r["collective_exposed_s"] == pytest.approx(r["collective_s"])
    # independent of the interval code, on device 0
    ops = events["devices"]["0"]["ops"]
    coll = [(s, s + d) for n, s, d in ops if n.startswith("all-reduce")]
    rest = [(s, s + d) for n, s, d in ops if not n.startswith("all-reduce")]
    lo = min(s for s, _ in coll)
    hi = max(e for _, e in coll)
    hidden = sum(1 for t in range(int(lo), int(hi), 500)
                 if any(s <= t < e for s, e in coll)
                 and any(s <= t < e for s, e in rest))
    assert hidden == 0
    assert r["idle_share_worst"] == pytest.approx(
        1 - min(r["busy_s_by_device"]) / r["window_s"])
