"""Device idle time by cause: every idle nanosecond of the traced ``fit``
call put down to what the program was doing for the step the device was
waiting for.

The program records its own spans (``analytics_zoo_tpu/obs/tracing``:
``fit``, ``fit_prepare``, each step's ``data_wait`` and ``train_step``
with the step's index ``i``, ``log_sync``, ``epoch_sync``,
``publish_counters`` on the caller's thread; ``host_batch`` and
``shard_batch`` with the same ``i`` on the input producer's), stamped on
a clock that its collector can put on CLOCK_REALTIME. The profiler's
trace says when it started on that clock (plane ``Task Environment``,
statistic ``profile_start_time``), so spans and device operations share
an axis with the host tracer off. Times inside are nanoseconds on the
trace's axis.

The rule, by step and not by midpoint (with asynchronous dispatch the
host is a step or more ahead of the device, so what the host is doing
*during* a gap belongs to a later step). On the device that was busy
least (``device_idle_share``'s device, the same busy union), for the
idle time between run ``k-1`` and run ``k`` of the step program:

- ``train_step`` ``k`` had returned before run ``k-1`` ended: the
  program was queued and the device still waited: ``device_queue`` (the
  runtime's, or a transfer's);
- otherwise the part before that return goes to what the caller's
  thread was in (``data_wait``, ``train_step``, ``log_sync``, another
  span by its name, ``fit_loop`` for the loop's own Python between
  spans) and the part after it to ``launch`` (dispatched, not yet
  started: the runtime's launch, or an input still on its way);
- idle time inside a run of the step program is ``inside_step``;
- before the first run there are three causes: ``fit_prepare``,
  ``first_batch`` (``data_wait`` 0) and ``train_step_0`` (all that
  follows the first batch's arrival: the loop's Python, the dispatch,
  and what a dispatched step 0 still waits for, such as its batch's
  transfer); after the last run ``epoch_sync``, ``publish_counters``
  and ``fit_return``;
- what lies outside the ``fit`` span (the rest of the runner's window)
  is ``no_span``.

Every idle nanosecond gets exactly one cause, so the causes add up to
``device_idle_share`` x the traced window. Before anything is
attributed the clock is checked on the run itself (:func:`clock_slacks`):
a wrong attribution is worse than none.

Two steps, like ``trace_reduce``: :func:`attribute` works on plain lists
(what ``tests/benchmark/data/*_spans.json`` holds), :func:`for_cell`
finds the cell's trace and the program's spans, once per process, and
prints the table to standard error.
"""

from __future__ import annotations

import bisect
import functools
import os
import statistics
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.lib import trace_reduce

Interval = Tuple[float, float]
# (name, start_ns, end_ns, i or None), on the trace's axis
Span = Tuple[str, float, float, Optional[int]]

CALLER = ("fit_prepare", "data_wait", "train_step", "log_sync",
          "epoch_sync", "publish_counters")
PRODUCER = ("host_batch", "shard_batch")
# the metrics' groups; with QUEUE, INSIDE and NO_SPAN they hold every cause
INPUT = ("first_batch", "data_wait")
HOST_LATE = ("train_step", "log_sync", "fit_loop", "launch")
CALL_EDGES = ("fit_prepare", "train_step_0", "epoch_sync",
              "publish_counters", "fit_return")
QUEUE, INSIDE, NO_SPAN = "device_queue", "inside_step", "no_span"
# in the order of a call
CAUSES = ("fit_prepare", "first_batch", "train_step_0", "data_wait",
          "train_step", "log_sync", "fit_loop", "launch", QUEUE, INSIDE,
          "epoch_sync", "publish_counters", "fit_return", NO_SPAN)
TASK_PLANE, PROFILE_START = "Task Environment", "profile_start_time"
CLOCK_TOLERANCE_NS = 1e6


def _say(message: str) -> None:
    print(f"host_spans: {message}", file=sys.stderr)


# ------------------------------------------------------------------ #
# the device's side                                                  #
# ------------------------------------------------------------------ #
def least_busy(events: dict) -> Optional[Tuple[str, List[Interval]]]:
    """``(device key, merged busy intervals)`` of the device that
    ``trace_reduce.reduce_trace`` calls the worst: the same operations
    (line ``XLA Ops`` without the control-flow wrappers), the same
    order, the first of the least busy."""
    best = None
    devices = events.get("devices", {})
    for key in sorted((k for k, v in devices.items() if v.get("ops")),
                      key=int):
        busy = trace_reduce.union(
            (s, s + d) for name, s, d in devices[key]["ops"]
            if not trace_reduce.CONTROL_FLOW.match(name))
        if best is None or trace_reduce.measure(busy) < best[2]:
            best = (key, busy, trace_reduce.measure(busy))
    return best[:2] if best else None


def step_runs(modules: Sequence) -> List[Interval]:
    """The runs of the step program on line ``XLA Modules``: the program
    that took the most time (the loop's other programs, the key's split
    and the scalars' placement, take microseconds)."""
    total: Dict[str, float] = {}
    for name, _, duration in modules:
        total[name] = total.get(name, 0.0) + duration
    if not total:
        return []
    step = max(total, key=total.get)
    return sorted((s, s + d) for name, s, d in modules if name == step)


# ------------------------------------------------------------------ #
# the clock                                                          #
# ------------------------------------------------------------------ #
def clock_slacks(runs: Sequence[Interval], last_op_end: float,
                 spans: Sequence[Span]) -> Optional[Tuple[float, float]]:
    """``(dispatch slack, sync slack)`` in ns, both of which a right
    clock keeps at or above 0: run ``k`` of the step program starts no
    earlier than ``train_step`` ``k`` began (the least over the steps),
    and the last ``epoch_sync`` ends no earlier than the last device
    operation. ``None`` where the runs and the ``train_step`` spans do
    not pair up one to one."""
    dispatched = sorted((i, s) for name, s, _, i in spans
                        if name == "train_step")
    syncs = [e for name, _, e, _ in spans if name == "epoch_sync"]
    if (not runs or not syncs or len(dispatched) != len(runs)
            or [i for i, _ in dispatched] != list(range(len(runs)))):
        return None
    return (min(run[0] - s for run, (_, s) in zip(runs, dispatched)),
            max(syncs) - last_op_end)


# ------------------------------------------------------------------ #
# attribution                                                        #
# ------------------------------------------------------------------ #
def _cause(name: str, i: Optional[int]) -> str:
    if i == 0 and name == "data_wait":
        return "first_batch"
    if i == 0 and name == "train_step":
        return "train_step_0"
    return name


def attribute(runs: Sequence[Interval], busy: Sequence[Interval],
              spans: Sequence[Span], window_ns: float) -> Optional[dict]:
    """``{"causes": {cause: ns}, "idle_ns", "window_ns", "steps",
    "waits": {i: ns}, "slacks": (ns, ns), "before_first": {cause: ns}}``
    (the last: the idle time before run 0 by the general rule, which
    ``causes`` folds into three) of one traced ``fit`` call:
    ``runs`` are the step program's runs and ``busy`` the merged busy
    intervals of one device, ``spans`` the call's spans, all on one
    axis; ``window_ns`` is the runner's traced window, whose idle time
    is ``window_ns - measure(busy)``. ``None``, with the reason on
    standard error, where the clock check fails by more than 1 ms or
    the call's spans do not fit the trace."""
    fits = [s for s in spans if s[0] == "fit"]
    if len(fits) != 1 or not busy:
        _say(f"nothing attributed: {len(fits)} fit spans, "
             f"{len(busy)} busy intervals")
        return None
    _, fit_start, fit_end, _ = fits[0]
    runs = sorted(runs)
    slacks = clock_slacks(runs, busy[-1][1], spans)
    if slacks is None:
        _say(f"nothing attributed: {len(runs)} runs of the step program "
             "do not pair with the call's train_step spans")
        return None
    _say(f"clock check: run k starts {slacks[0] / 1e3:.1f} us after "
         f"train_step k began (least over {len(runs)} steps); epoch_sync "
         f"ends {slacks[1] / 1e3:.1f} us after the last device operation "
         "(both must be >= 0)")
    if min(slacks) < -CLOCK_TOLERANCE_NS:
        _say("nothing attributed: the spans' clock and the trace's "
             "disagree by more than 1 ms")
        return None

    caller = sorted((s, e, _cause(name, i), i) for name, s, e, i in spans
                    if name in CALLER)
    caller_starts = [c[0] for c in caller]
    last_child_end = max((c[1] for c in caller), default=fit_start)
    returned = {i: e for name, _, e, i in spans if name == "train_step"}
    causes = {c: 0.0 for c in CAUSES}
    before_first = dict(causes)     # idle time before run 0, folded below
    waits: Dict[int, float] = {}

    def uncovered(a: float, b: float, causes: Dict[str, float]) -> None:
        """Inside ``fit``, in no other span of the caller's thread."""
        cut = min(max(last_child_end, a), b)
        causes["fit_loop"] += cut - a
        causes["fit_return"] += b - cut

    def by_caller(a: float, b: float, causes: Dict[str, float]) -> None:
        at = a
        for s, e, cause, i in caller[max(
                bisect.bisect_right(caller_starts, a) - 1, 0):]:
            if s >= b:
                break
            if e <= at:
                continue
            if s > at:
                uncovered(at, s, causes)
            lo, hi = max(s, at), min(e, b)
            causes[cause] += hi - lo
            if cause in INPUT:
                waits[i] = waits.get(i, 0.0) + hi - lo
            at = hi
        if at < b:
            uncovered(at, b, causes)

    cuts = sorted({t for run in runs for t in run})
    ends = [e for _, e in runs]
    for gap in trace_reduce.gaps(busy, fit_start, fit_end):
        edges = [gap[0], *cuts[bisect.bisect_right(cuts, gap[0]):
                               bisect.bisect_left(cuts, gap[1])], gap[1]]
        for a, b in zip(edges, edges[1:]):
            k = bisect.bisect_right(ends, (a + b) / 2)    # the run ahead
            if k == len(runs):
                by_caller(a, b, causes)                   # after the last
            elif runs[k][0] <= a:
                causes[INSIDE] += b - a
            else:
                began = runs[k - 1][1] if k else fit_start
                into = causes if k else before_first
                if returned[k] <= began:
                    into[QUEUE] += b - a
                else:
                    cut = min(max(returned[k], a), b)
                    if cut > a:
                        by_caller(a, cut, into)
                    into["launch"] += b - cut
    # before the first run there are three causes: the call's
    # preparation, the first batch's wait, and step 0 (the loop's Python
    # after the batch came, the dispatch, and the time a dispatched
    # program took to start: its launch and its inputs' arrival)
    for cause, ns in before_first.items():
        causes[cause if cause in ("fit_prepare", "first_batch")
               else "train_step_0"] += ns
    idle_ns = window_ns - trace_reduce.measure(busy)
    causes[NO_SPAN] = idle_ns - sum(causes.values())
    if causes[NO_SPAN] < -1e3:
        _say("nothing attributed: the device was busy for "
             f"{-causes[NO_SPAN] / 1e3:.1f} us outside the fit span")
        return None
    return {"causes": causes, "idle_ns": idle_ns, "window_ns": window_ns,
            "steps": len(runs), "waits": waits, "slacks": slacks,
            "before_first": {c: ns for c, ns in before_first.items() if ns}}


def host_step_ms(spans: Sequence[Span],
                 runs: Sequence[Interval]) -> Optional[float]:
    """The caller's thread per step outside ``data_wait``: from the
    return of ``data_wait`` ``i`` to the start of the next wait (the
    next ``data_wait`` or the epoch's ``epoch_sync``) less the step's
    ``log_sync``: the dispatch and the loop's own Python. The median
    over the steps the runtime did not hold back: once the device's
    queue is as deep as the runtime lets it grow, the caller's next
    dispatch blocks until a run ends, and a step then reads the
    device's time, not the host's cost. A step counts when, at the
    return of its ``train_step``, fewer programs were dispatched and
    unfinished than at the call's deepest (all steps where the depth
    never varies)."""
    waits = sorted((i, s, e) for name, s, e, i in spans if name == "data_wait")
    nexts = sorted([s for _, s, _ in waits]
                   + [s for name, s, _, _ in spans if name == "epoch_sync"])
    synced = {i: e - s for name, s, e, i in spans if name == "log_sync"}
    returned = {i: e for name, _, e, i in spans if name == "train_step"}
    ends = sorted(e for _, e in runs)
    cost, depth = {}, {}
    for i, _, e in waits:
        k = bisect.bisect_left(nexts, e)
        if k < len(nexts) and i in returned:
            cost[i] = nexts[k] - e - synced.get(i, 0.0)
            depth[i] = i + 1 - bisect.bisect_right(ends, returned[i])
    if not cost:
        return None
    free = [cost[i] for i in cost if depth[i] < max(depth.values())]
    return statistics.median(free or cost.values()) / 1e6


# ------------------------------------------------------------------ #
# the report                                                         #
# ------------------------------------------------------------------ #
def report(result: dict, spans: Sequence[Span]) -> None:
    """The table (cause, ms a call, ms a step, share of idle) and, for
    every wait for a batch that left the device idle, the producer's two
    spans of the same ``i``: the spans that caused the wait."""
    out = sys.stderr
    idle, steps = result["idle_ns"], result["steps"]
    print(f"host_spans: device idle by cause, traced fit call of {steps} "
          f"steps: {idle / 1e6:.3f} ms idle of "
          f"{result['window_ns'] / 1e6:.3f} ms "
          f"({100 * idle / result['window_ns']:.3f} %)", file=out)
    print(f"  {'ms a call':>10} {'ms a step':>10} {'of idle':>8}  cause",
          file=out)
    for cause, ns in result["causes"].items():
        if ns:
            print(f"  {ns / 1e6:10.3f} {ns / 1e6 / steps:10.4f} "
                  f"{100 * ns / idle:7.2f}%  {cause}", file=out)
    print("  before run 0, unfolded: " + ", ".join(
        f"{cause} {ns / 1e6:.3f}" for cause, ns in
        result["before_first"].items()) + " ms", file=out)
    producer = {(name, i): (e - s) / 1e6 for name, s, e, i in spans
                if name in PRODUCER}
    for i, ns in sorted(result["waits"].items()):
        made, placed = (producer.get((name, i)) for name in PRODUCER)
        print(f"  data_wait {i}: device idle {ns / 1e6:.3f} ms; the "
              f"producer's host_batch {i} took "
              f"{'?' if made is None else format(made, '.3f')} ms, "
              f"shard_batch {i} "
              f"{'?' if placed is None else format(placed, '.3f')} ms",
              file=out)


# ------------------------------------------------------------------ #
# the cell's trace and the program's spans, once per process         #
# ------------------------------------------------------------------ #
def profile_start_ns(path: str) -> Optional[int]:
    """CLOCK_REALTIME nanoseconds at which the profiler started: the
    zero of the trace's axis."""
    import jax

    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == TASK_PLANE:
            return dict(plane.stats).get(PROFILE_START)
    return None


def last_call_spans(zero_ns: int) -> Optional[List[Span]]:
    """The spans of the newest ``fit`` call in the program's ring, on
    the axis whose zero is ``zero_ns`` of CLOCK_REALTIME. ``None`` where
    the program records none (a tree from before the train path had
    spans)."""
    from analytics_zoo_tpu.obs import tracing

    tracer = tracing.get_tracer()
    if not hasattr(tracer, "wall_ns"):
        return None
    ring = tracer.spans()
    fits = [s for s in ring if s["name"] == "fit"]
    if not fits:
        return None
    spans = []
    for s in ring:
        if s["trace_id"] == fits[-1]["trace_id"]:
            start, end = tracer.wall_ns(s)
            spans.append((s["name"], float(start - zero_ns),
                          float(end - zero_ns),
                          (s.get("args") or {}).get("i")))
    return spans


@functools.lru_cache(maxsize=None)
def _attributed_file(path: str, window_s: float) -> Optional[dict]:
    zero_ns = profile_start_ns(path)
    spans = last_call_spans(zero_ns) if zero_ns is not None else None
    if not spans:
        _say("nothing attributed: " + (
            "the trace has no profile_start_time" if zero_ns is None else
            "the program recorded no fit span"))
        return None
    events = trace_reduce.load_xplane(path)
    device = least_busy(events)
    if device is None:
        return None
    key, busy = device
    runs = step_runs(events["devices"][key]["modules"])
    result = attribute(runs, busy, spans, window_s * 1e9)
    if result:
        result["device"] = key
        result["host_step_ms"] = host_step_ms(spans, runs)
        report(result, spans)
    return result


def for_cell(ctx: dict) -> Optional[dict]:
    """:func:`attribute` of the traced epoch the runner left under the
    cell's scratch directory and of the program's last ``fit`` call
    (which is that epoch: nothing the runner does after it calls
    ``fit``); ``None`` where there is no device trace, as in a CPU
    rehearsal, no span, or a clock that fails its check."""
    if not ctx.get("trace"):
        return None
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = trace_reduce.find_xplane(os.path.join(
        bench_dir, ".cache", "scratch", ctx["cell"]["name"], "trace"))
    if path is None:
        return None
    return _attributed_file(path, ctx["trace"]["window_s"])


def idle_ms(ctx: dict, causes: Sequence[str]) -> Optional[float]:
    """Device idle milliseconds of the traced call put down to
    ``causes``; ``None`` where :func:`for_cell` has nothing."""
    result = for_cell(ctx)
    if not result:
        return None
    return sum(result["causes"][c] for c in causes) / 1e6


def first_span_s(name: str) -> Optional[float]:
    """Seconds of the process's first span of that name; ``None`` where
    the ring has dropped a span since (or cannot say)."""
    from analytics_zoo_tpu.obs import tracing

    tracer = tracing.get_tracer()
    if getattr(tracer, "dropped", None) != 0:
        return None
    first = next((s for s in tracer.spans() if s["name"] == name), None)
    return first["t1"] - first["t0"] if first else None
