"""Device time by name: the traced epoch's operations summed by phase
(forward, backward, optimizer, collective, other) and by module path.

The names are the program's own. Every HLO instruction carries the
``op_name`` JAX gave the operation it came from, e.g.
``jit(step)/transpose(jvp(BERTForSQuAD))/squad/bert/encoder_3/ffn_in/dot_general``:
flax puts each module's path there, JAX marks forward operations
``jvp(...)`` and backward ones ``transpose(jvp(...))``, and the program
scopes what no module names (``optimizer``, ``loss``, ``grad_accum``,
``attention_<path>``). The TPU runtime writes it into the trace as the
statistic ``tf_op`` of the instruction's event metadata, next to
``hlo_category``, ``flops`` and ``bytes_accessed`` (XLA's own cost
analysis), which ``lib/xplane_meta.py`` reads. A trace whose device
planes hold no ``tf_op`` is read through the programs that plane
``/host:metadata`` keeps (``HloProto``).

Two steps, like ``trace_reduce``: :func:`load_scoped` turns an
``.xplane.pb`` into one plain row per instruction (what
``tests/benchmark/data/*_meta.json`` holds) and :func:`reduce_scopes`
turns the rows into tables. The operations are the ones
``trace_reduce.reduce_trace`` calls busy: line ``XLA Ops`` without the
control-flow wrappers, mean over the chips used. On that line the
operations of a chip do not overlap, so their durations add up to the
busy time (``train_step_device_ms``); :func:`for_cell` prints both.

A compile cache that an older tree filled serves that tree's names: the
cache's key is taken after debug information is stripped, so a changed
scope does not miss. The first trace after a scope changes needs a
fresh ``JAX_COMPILATION_CACHE_DIR``.
"""

from __future__ import annotations

import functools
import os
import re
import sys
import time
from typing import Dict, List, Optional

from benchmark.lib import trace_reduce, xplane_meta

PHASES = ("forward", "backward", "optimizer", "collective", "other")
NO_OP_NAME = "(no op_name)"
HLO_PROTO_PLANE, HLO_PROTO_STAT = "/host:metadata", "Hlo Proto"
# one row of load_scoped
HLO, OP_NAME, CATEGORY, FLOPS, BYTES, RUNS, DURATION_NS = range(7)
TABLE_ROWS, OTHER_OPS = 15, 10     # what report() prints

_ATTENTION = re.compile(r"/(attention_[a-z_]+)/")
_TRANSFORM = re.compile(r"^\w+\(.*\)$")          # jvp(...), jit(...), vmap(...)
_WRAPPED = re.compile(r"\(([^()]*)\)")           # innermost argument
_LAYER_INDEX = re.compile(r"(_|block)\d+$")      # encoder_3, stage2_block1


def phase(hlo_name: str, op_name: str) -> str:
    """The rule, written once. A collective is one by its HLO name,
    whatever its ``op_name`` says (a gradient all-reduce says
    ``transpose(``). ``backward`` includes the forward operations that
    are rematerialised for it: JAX names them under ``transpose(`` too.
    ``other`` is what carries no ``op_name`` (copies and slices XLA
    inserted) or none of the marks."""
    if trace_reduce.COLLECTIVE.match(hlo_name):
        return "collective"
    if "/optimizer/" in op_name:
        return "optimizer"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return "other"


def module_path(op_name: str) -> str:
    """``jit(step)/jvp(BERTForSQuAD)/squad/bert/encoder_0/ffn_in/dot_general``
    -> ``squad/bert/encoder_0/ffn_in``: the components between the
    ``jvp(...)`` element (or the leading ``jit(...)`` where there is
    none, as under ``optimizer``) and the final primitive, function
    transforms such as ``jit(gelu)`` left out. Where nothing is between
    them, the name the ``jvp`` element wraps: ``jvp(loss)/mul`` ->
    ``loss``. Of a fusion's several names the first counts."""
    parts = op_name.split(";")[0].rstrip(":").split("/")
    if not parts[0]:
        return NO_OP_NAME
    mark = next((i for i, p in enumerate(parts)
                 if p.startswith(("jvp(", "transpose("))), 0)
    between = [p for p in parts[mark:-1] if not _TRANSFORM.match(p)]
    if between:
        return "/".join(between)
    wrapped = _WRAPPED.search(parts[mark])
    return wrapped.group(1) if wrapped and wrapped.group(1) else parts[mark]


def without_layer_index(path: str) -> str:
    """``squad/bert/encoder_3/ffn_in`` -> ``squad/bert/encoder_*/ffn_in``,
    ``backbone/stage2_block1/bn3`` -> ``backbone/stage2_block*/bn3``."""
    return "/".join(_LAYER_INDEX.sub(r"\1*", p) for p in path.split("/"))


def layer_of(path: str) -> str:
    """The path up to its first indexed component: one encoder layer,
    one residual block."""
    parts = path.split("/")
    for i, p in enumerate(parts):
        if _LAYER_INDEX.search(p):
            return "/".join(parts[:i + 1])
    return path


# ------------------------------------------------------------------ #
# loading                                                            #
# ------------------------------------------------------------------ #
def load_scoped(path: str) -> dict:
    """``{"devices": {"0": [[hlo name, op_name, hlo_category, flops,
    bytes_accessed, runs, duration_ns], ...]}, "op_name_from": ...}``:
    one row per instruction that ran on line ``XLA Ops``, control-flow
    wrappers left out; ``flops`` and ``bytes_accessed`` are of one run
    (``None`` where the trace has none), ``duration_ns`` is the sum
    over the runs. ``op_name_from`` says where the names were found:
    ``"tf_op"``, ``"hlo_proto"`` or ``None``."""
    import jax

    metadata = xplane_meta.read_metadata_file(path)
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    for plane in data.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        stats_of = metadata.get(plane.name, {})
        rows = devices.setdefault(m.group(1), {})
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for event in line.events:
                row = rows.get(event.name)
                if row is None:
                    hlo = trace_reduce.op_name(event.name)
                    if trace_reduce.CONTROL_FLOW.match(hlo):
                        continue
                    stats = stats_of.get(event.name, {})
                    row = rows[event.name] = [
                        hlo, str(stats.get("tf_op", "")).rstrip(":"),
                        str(stats.get("hlo_category", "")),
                        stats.get("flops"), stats.get("bytes_accessed"),
                        0, 0.0]
                row[RUNS] += 1
                row[DURATION_NS] += event.duration_ns
    out = {"devices": {k: list(v.values()) for k, v in devices.items()},
           "op_name_from": None}
    if any(r[OP_NAME] for rows in out["devices"].values() for r in rows):
        out["op_name_from"] = "tf_op"
    else:
        names: Dict[str, str] = {}
        for stats in metadata.get(HLO_PROTO_PLANE, {}).values():
            if isinstance(stats.get(HLO_PROTO_STAT), bytes):
                names.update(xplane_meta.hlo_op_names(stats[HLO_PROTO_STAT]))
        for rows in out["devices"].values():
            for r in rows:
                r[OP_NAME] = names.get(r[HLO], "")
                if r[OP_NAME]:
                    out["op_name_from"] = "hlo_proto"
    return out


# ------------------------------------------------------------------ #
# reduction                                                          #
# ------------------------------------------------------------------ #
def _table(rows_by_key: Dict[str, List[tuple]], scale: float,
           total_ns: float, peaks: Optional[dict]) -> List[dict]:
    """One row per key, longest first. ``flops_share`` and
    ``bytes_share`` are the two sides of the row's own roofline: the
    time its FLOPs (its bytes) would take at the chip's peak over the
    time it took, by XLA's count of each instruction. The larger one is
    the row's roofline share. XLA's ``bytes_accessed`` counts every
    operand of every fused operation, those served from on-chip memory
    too, so ``bytes_share`` can pass 1: it is printed as counted, not
    capped."""
    table = []
    for key, rows in rows_by_key.items():
        ns = {p: 0.0 for p in PHASES}
        by_category: Dict[str, float] = {}
        flops = bytes_ = 0.0
        counted = True
        for which, row in rows:
            ns[which] += row[DURATION_NS]
            by_category[row[CATEGORY]] = (by_category.get(row[CATEGORY], 0.0)
                                          + row[DURATION_NS])
            if row[FLOPS] is None or row[BYTES] is None:
                counted = False
            else:
                flops += row[FLOPS] * row[RUNS]
                bytes_ += row[BYTES] * row[RUNS]
        all_ns = sum(ns.values())
        rated = counted and peaks and all_ns
        table.append({
            "scope": key,
            "forward_ms": ns["forward"] * scale,
            "backward_ms": ns["backward"] * scale,
            "total_ms": all_ns * scale,
            "share": all_ns / total_ns,
            "hlo_category": max(by_category, key=by_category.get),
            # per step and chip; None where the trace carries no counts
            # for one of the row's instructions
            "gflops": flops * scale / 1e3 if counted else None,
            "mbytes": bytes_ * scale if counted else None,
            "flops_share": (1e9 * flops / peaks["bf16_flops_per_s"] / all_ns
                            if rated else None),
            "bytes_share": (1e9 * bytes_ / peaks["hbm_bytes_per_s"] / all_ns
                            if rated else None),
        })
    return sorted(table, key=lambda r: -r["total_ms"])


def reduce_scopes(scoped: dict, steps: int,
                  peaks: Optional[dict] = None) -> Optional[dict]:
    """Milliseconds per step and chip. ``None`` when no operation ran on
    a device."""
    devices = {k: v for k, v in scoped.get("devices", {}).items() if v}
    if not devices:
        return None
    scale = 1e-6 / (len(devices) * steps)      # ns over all chips -> ms a step
    phases = {p: 0.0 for p in PHASES}
    attention: Dict[str, float] = {}
    modules: Dict[str, List[tuple]] = {}
    layers: Dict[str, List[tuple]] = {}
    other: Dict[str, float] = {}
    named_ns = 0.0
    for rows in devices.values():
        for row in rows:
            which = phase(row[HLO], row[OP_NAME])
            phases[which] += row[DURATION_NS]
            if row[OP_NAME]:
                named_ns += row[DURATION_NS]
            m = _ATTENTION.search(row[OP_NAME])
            if m:
                attention[m.group(1)] = (attention.get(m.group(1), 0.0)
                                         + row[DURATION_NS] * scale)
            path = module_path(row[OP_NAME])
            modules.setdefault(without_layer_index(path), []).append(
                (which, row))
            layers.setdefault(layer_of(path), []).append((which, row))
            if which == "other":
                other[row[HLO]] = other.get(row[HLO], 0.0) + row[DURATION_NS]
    total_ns = sum(phases.values())
    return {
        "op_name_from": scoped.get("op_name_from"),
        "total_ms": total_ns * scale,
        "phases_ms": {p: ns * scale for p, ns in phases.items()},
        "attributed_share": 1.0 - phases["other"] / total_ns,
        "named_share": named_ns / total_ns,
        "attention_ms": attention,
        "modules": _table(modules, scale, total_ns, peaks),
        "layers": _table(layers, scale, total_ns, peaks),
        "other_ops": [[name, ns * scale] for name, ns in
                      sorted(other.items(), key=lambda kv: -kv[1])[:OTHER_OPS]],
    }


# ------------------------------------------------------------------ #
# the cell's trace, once per process                                 #
# ------------------------------------------------------------------ #
def _print_table(title: str, table: List[dict]) -> None:
    out = sys.stderr
    print(f"scope_reduce: {title} (ms per step and chip; GFLOP and MB by "
          "XLA's count, flop% and byte% = their time at the chip's peak "
          "over the time taken)", file=out)
    print(f"  {'forward':>8} {'backward':>8} {'total':>8} {'share':>6} "
          f"{'GFLOP':>8} {'flop%':>6} {'MB':>8} {'byte%':>6}  "
          "scope [main category]", file=out)

    def num(v, spec, times=1.0):
        return format(v * times, spec) if v is not None else "-"

    for r in table[:TABLE_ROWS]:
        print(f"  {r['forward_ms']:8.3f} {r['backward_ms']:8.3f} "
              f"{r['total_ms']:8.3f} {100 * r['share']:5.1f}% "
              f"{num(r['gflops'], '8.1f'):>8} "
              f"{num(r['flops_share'], '6.1f', 100):>6} "
              f"{num(r['mbytes'], '8.1f'):>8} "
              f"{num(r['bytes_share'], '6.1f', 100):>6}  "
              f"{r['scope']} [{r['hlo_category']}]", file=out)


def report(reduced: dict, busy_ms: float, seconds: float) -> None:
    """The phases, the two scope tables and ``other``'s largest
    operations, to standard error."""
    out = sys.stderr
    phases = reduced["phases_ms"]
    print("scope_reduce: " + ", ".join(
        f"{p} {phases[p]:.3f}" for p in PHASES)
        + f" = {reduced['total_ms']:.3f} ms per step and chip "
        f"(device-busy union {busy_ms:.3f}); op names from "
        f"{reduced['op_name_from']}, on {100 * reduced['named_share']:.2f} % "
        f"of the time; attention paths {reduced['attention_ms'] or 'none'}; "
        f"read and reduced in {seconds:.2f} s", file=out)
    _print_table("by module, layer indices as *", reduced["modules"])
    _print_table("by layer", reduced["layers"])
    print("scope_reduce: largest ops in 'other' (ms per step and chip): "
          + ", ".join(f"{name} {ms:.3f}" for name, ms in
                      reduced["other_ops"]), file=out)


@functools.lru_cache(maxsize=None)
def _reduced_file(path: str, steps: int, device_kind: str,
                  busy_ms: float) -> Optional[dict]:
    from benchmark.lib.peaks import peaks_for

    started = time.perf_counter()
    reduced = reduce_scopes(load_scoped(path), steps,
                            peaks=peaks_for(device_kind))
    if reduced:
        report(reduced, busy_ms, time.perf_counter() - started)
    return reduced


def for_cell(ctx: dict) -> Optional[dict]:
    """The reduction of the traced epoch the runner left under the
    cell's scratch directory, read once per process (the first reader
    prints the tables to standard error); ``None`` where the trace has
    no device plane, as in a CPU rehearsal."""
    if not ctx.get("trace"):
        return None
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = trace_reduce.find_xplane(os.path.join(
        bench_dir, ".cache", "scratch", ctx["cell"]["name"], "trace"))
    if path is None:
        return None
    steps = ctx["window"]["steps_per_epoch"]
    return _reduced_file(path, steps, ctx["device_kind"],
                         1e3 * ctx["trace"]["busy_s"] / steps)


def phase_ms(ctx: dict, which: str) -> Optional[float]:
    """Device milliseconds per step in one phase of the cell's traced
    epoch; ``None`` where :func:`for_cell` has nothing."""
    scopes = for_cell(ctx)
    return scopes["phases_ms"][which] if scopes else None
