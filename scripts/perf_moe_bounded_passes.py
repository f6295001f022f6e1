"""On-chip timings behind the bounded passes of ``DroplessExperts``
(docs/kernels.md "Bounded passes"): one expert layer at the published
widths (16 of 128 experts held, d 2048, width 1024, 8,192 tokens, 8 a
token, bf16), forward + backward, at a given load of the worst-case
buffer; device time by scope and by operation from a profiler trace.
Run through the chip tool; prints one JSON line per reading and the
tables on standard error.

    python scripts/perf_moe_bounded_passes.py [--repo DIR] [--load 1.25 ...]
        [--variant BUFFER_TILE=1024,SEGMENT_BLOCK=256 ...] [--ops 30]

``--repo`` names another checkout of this repository to time (the
parent commit, unpacked). A ``--variant`` sets integer constants of
``keras/layers/moe.py`` before the layer is traced, and is skipped
where the module has no such constant.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N, D, WIDTH, ROUTED, HELD, TOP_K = 8192, 2048, 1024, 128, 16, 8
STEPS = 5


def report(**kv):
    print(json.dumps(kv), flush=True)


def bias_for(load, scores, jnp, jax):
    """A bias on the held experts under which ``load * N`` of the
    ``N * TOP_K`` assignments fall on them (bisection on the device)."""
    held = jnp.arange(ROUTED) < HELD

    def count(delta):
        _, idx = jax.lax.top_k(scores + jnp.where(held, delta, 0.0), TOP_K)
        return int(jnp.sum(idx < HELD))

    lo, hi = -1.0, 1.0
    for _ in range(24):
        mid = (lo + hi) / 2
        if count(mid) < load * N:
            lo = mid
        else:
            hi = mid
    return jnp.where(held, hi, 0.0), count(hi)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--repo", default=HERE)
    parser.add_argument("--load", type=float, nargs="+", default=[1.25])
    parser.add_argument("--variant", nargs="+", default=[""])
    parser.add_argument("--ops", type=int, default=30)
    parser.add_argument("--rehearse", action="store_true",
                        help="a tiny layer on whatever backend there is: "
                        "checks the script, times nothing")
    args = parser.parse_args()
    if args.rehearse:
        global N, D, WIDTH
        N, D, WIDTH = 256, 128, 128
    sys.path.insert(0, os.path.abspath(args.repo))
    sys.path.insert(1, HERE)            # benchmark/lib, where --repo has none

    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.keras.layers import moe
    from benchmark.lib import scope_reduce, trace_reduce
    from benchmark.lib.peaks import peaks_for

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        raise SystemExit("perf_moe_bounded_passes: needs a TPU")
    module = moe.DroplessExperts(
        width=WIDTH, n_routed=ROUTED, n_held=HELD, top_k=TOP_K,
        route_scale=2.826, shared_width=0, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, N, D), jnp.bfloat16)
    variables = jax.jit(module.init)(jax.random.PRNGKey(0), x)
    params = variables.pop("params")
    scores = jax.nn.sigmoid(
        x[0].astype(jnp.float32) @ params["router"]["kernel"])
    ct = jax.random.normal(jax.random.PRNGKey(2), x.shape, jnp.bfloat16)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0

    for variant in args.variant:
        for setting in filter(None, variant.split(",")):
            name, _, value = setting.partition("=")
            if hasattr(moe, name):
                setattr(moe, name, int(value))
        for load in args.load:
            bias, held = bias_for(load, scores, jnp, jax)

            def loss(params, x):
                out = module.apply(
                    {**variables, "params": params,
                     "router_state": {"bias": bias}}, x)
                return jnp.sum((out * ct).astype(jnp.float32))

            step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
            started = time.perf_counter()
            out = jax.block_until_ready(step(params, x))
            compile_s = time.perf_counter() - started
            finite = all(bool(jnp.isfinite(leaf).all()) for leaf in
                         jax.tree_util.tree_leaves(out))
            started = time.perf_counter()
            for _ in range(STEPS):
                out = step(params, x)
            jax.block_until_ready(out)
            host_ms = 1e3 * (time.perf_counter() - started) / STEPS
            trace_dir = tempfile.mkdtemp()
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            for _ in range(STEPS):
                out = step(params, x)
            jax.block_until_ready(out)
            jax.profiler.stop_trace()
            scoped = scope_reduce.load_scoped(
                trace_reduce.find_xplane(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
            if args.rehearse:
                report(rehearsal=True, held=held, finite=finite)
                continue
            reduced = scope_reduce.reduce_scopes(
                scoped, STEPS,
                peaks=peaks_for(jax.devices()[0].device_kind))
            by_scope = {}
            for row in reduced["modules"]:
                for scope in ("moe_route", "moe_dispatch", "moe_experts",
                              "moe_combine"):
                    if scope in row["scope"].split("/"):
                        got = by_scope.setdefault(scope, [0.0, 0.0])
                        got[0] += row["forward_ms"]
                        got[1] += row["backward_ms"]
            report(repo=args.repo, variant=variant, load=load,
                   held=held, finite=finite, compile_s=compile_s,
                   host_ms=host_ms, device_ms=reduced["total_ms"],
                   scopes_fwd_bwd_ms=by_scope)
            scope_reduce._print_table(
                f"{args.repo} {variant} load {load}",
                reduced["modules"])
            rows = sorted(
                (r for dev in scoped["devices"].values() for r in dev),
                key=lambda r: -r[scope_reduce.DURATION_NS])
            for r in rows[:args.ops]:
                print(f"  {r[scope_reduce.DURATION_NS] / STEPS / 1e6:8.3f}"
                      f" ms {r[scope_reduce.RUNS] / STEPS:6.1f} runs  "
                      f"{r[scope_reduce.HLO]:28s} "
                      f"{r[scope_reduce.OP_NAME][-110:]}",
                      file=sys.stderr)


if __name__ == "__main__":
    main()
