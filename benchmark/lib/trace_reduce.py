"""From a ``jax.profiler`` trace to numbers: device busy union, idle
share and gaps, the table of device operations, collective time and
the part of it that no compute hides.

Two steps, so that the arithmetic can be checked on a small recorded
trace without a chip: :func:`load_xplane` turns an ``.xplane.pb`` into
plain lists (what ``tests/benchmark/data/*.json`` holds), and
:func:`reduce_trace` turns those lists into metrics. All times inside
are nanoseconds on the profiler's clock; results are seconds.

What the TPU runtime writes (seen on a v5e, PR 22): one plane per chip
named ``/device:TPU:<n>`` with the lines ``XLA Ops`` (one event per HLO
instruction executed, named by its HLO text), ``Async XLA Ops`` (one
span from each ``*-start`` to its ``*-done``), ``XLA Modules`` (one
event per program execution) and ``Steps``; and one plane ``/host:CPU``
whose lines are host threads, where ``jax.profiler.TraceAnnotation``
spans appear under their own names.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
Event = Tuple[str, float, float]        # (name, start_ns, duration_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
# wrappers whose event covers their whole body: they are neither compute
# nor idle of their own
CONTROL_FLOW = re.compile(r"^(while|conditional|call)(\.|$)")
_HLO_NAME = re.compile(r"^%?([^ =]+)")
_MODULE_HASH = re.compile(r"\(\d+\)$")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


# ------------------------------------------------------------------ #
# interval arithmetic                                                #
# ------------------------------------------------------------------ #
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping; empty intervals dropped."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``merged`` does not cover."""
    out, at = [], lo
    for s, e in clip(merged, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out: List[Interval] = []
    for s, e in a:
        out.extend(gaps(b, s, e))
    return out


def _intervals(events: Iterable[Event]) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


# ------------------------------------------------------------------ #
# loading                                                            #
# ------------------------------------------------------------------ #
def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_xplane(path: str,
                host_span: Optional[Callable[[str], bool]] = None) -> dict:
    """``{"devices": {"0": {"ops": [...], "async": [...], "modules":
    [...]}}, "host": [...]}`` with events as ``[name, start_ns,
    duration_ns]``. Device events are named by their HLO instruction
    name; of the host's events only those ``host_span`` accepts are kept
    (a trace taken with the host tracer on holds millions of runtime
    events), and none when it is not given."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: dict = {"devices": {}, "host": []}
    keep = {OPS_LINE: "ops", ASYNC_LINE: "async", MODULES_LINE: "modules"}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(
                m.group(1), {"ops": [], "async": [], "modules": []})
            for line in plane.lines:
                key = keep.get(line.name)
                if key is None:
                    continue
                shorten = (op_name if key != "modules"
                           else lambda n: _MODULE_HASH.sub("", n))
                dev[key].extend([shorten(e.name), e.start_ns, e.duration_ns]
                                for e in line.events)
        elif plane.name == HOST_PLANE and host_span is not None:
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.duration_ns]
                                   for e in line.events
                                   if host_span(e.name))
    return out


# ------------------------------------------------------------------ #
# reduction                                                          #
# ------------------------------------------------------------------ #
def _label(gap: Interval, host: Sequence[Event]) -> str:
    """The innermost host span that holds the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for name, s, d in host:
        if s <= mid <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no_span"


OUTSIDE = "before_first_or_after_last_op"


def reduce_trace(events: dict, window: Optional[Interval] = None,
                 window_s: Optional[float] = None,
                 top: int = 10) -> Optional[dict]:
    """Metrics of one traced window; ``None`` when no operation ran on a
    device (nothing to read). ``window`` defaults to the extent of the
    device operations and the kept host spans together. ``window_s`` is
    the window's length on the host's clock, for a trace that holds no
    host span: what it exceeds the extent by is idle time before the
    first or after the last operation, and is listed as one gap."""
    devices = {k: v for k, v in events.get("devices", {}).items()
               if v.get("ops")}
    if not devices:
        return None
    host = [tuple(e) for e in events.get("host", [])]
    if window is None:
        spans = _intervals(host)
        for dev in devices.values():
            spans += _intervals(dev["ops"])
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    lo, hi = window
    outside_ns = max(0.0, (window_s or 0.0) * 1e9 - (hi - lo))
    window_ns = hi - lo + outside_ns

    per_device = []
    op_totals: Dict[str, float] = {}
    module_runs: Dict[str, float] = {}
    for key in sorted(devices, key=int):
        dev = devices[key]
        real_ops = [e for e in dev["ops"] if not CONTROL_FLOW.match(e[0])]
        busy = clip(union(_intervals(real_ops)), lo, hi)
        compute = union(_intervals(
            e for e in real_ops if not COLLECTIVE.match(e[0])))
        coll = clip(union(_intervals(
            e for e in real_ops + list(dev.get("async", []))
            if COLLECTIVE.match(e[0]))), lo, hi)
        idle = gaps(busy, lo, hi)
        if outside_ns:
            idle.append((hi, hi + outside_ns))
        per_device.append({
            "device": key,
            "busy_ns": measure(busy),
            "collective_ns": measure(coll),
            "collective_exposed_ns": measure(subtract(coll, compute)),
            "gaps": sorted(idle, key=lambda g: g[0] - g[1])[:top],
        })
        for name, _, dur in real_ops:
            op_totals[name] = op_totals.get(name, 0.0) + dur
        for name, s, _ in dev.get("modules", []):
            if lo <= s <= hi:
                module_runs[name] = module_runs.get(name, 0) + 1
    n = len(per_device)
    worst = min(per_device, key=lambda d: d["busy_ns"])
    ops = sorted(op_totals.items(), key=lambda kv: -kv[1])[:top]
    return {
        "devices": n,
        "window_s": window_ns / 1e9,
        "busy_s": sum(d["busy_ns"] for d in per_device) / n / 1e9,
        "busy_s_by_device": [d["busy_ns"] / 1e9 for d in per_device],
        "idle_share_worst": 1.0 - worst["busy_ns"] / window_ns,
        "collective_s": sum(d["collective_ns"] for d in per_device) / n / 1e9,
        "collective_exposed_s": sum(d["collective_exposed_ns"]
                                    for d in per_device) / n / 1e9,
        # operation seconds are per device (summed over devices / n)
        "ops": [[name, ns / n / 1e9] for name, ns in ops],
        "idle_gaps": [[OUTSIDE if g[0] >= hi else _label(g, host),
                       (g[1] - g[0]) / 1e9] for g in worst["gaps"]],
        "module_runs": {k: v / n for k, v in module_runs.items()},
    }
