#!/usr/bin/env python
"""Generation serving soak + A/B (ISSUE-10).

Drives the REAL token-streaming data plane -- InputQueue ->
GenerationWorker (continuous batcher over the paged-KV DecodeEngine)
-> chunked replies on the OutputQueue -- with overlapping request
lifetimes (a bounded admission window keeps ``--concurrency`` streams
alive at once), then verifies the contract the acceptance criteria
name:

- **exactly-once**: every request's chunk seqs are contiguous from 0
  with exactly one terminal chunk, nothing unanswered, no duplicates;
- **token-exact**: every stream's tokens equal a SOLO decode of the
  same prompt (fresh single-slot engine, same params) -- continuous
  batching changes scheduling, never results;
- **zero recompile storms** (and zero live generation compiles) after
  warm-up -- the prefill ladder + fixed-shape decode step really do
  pin the XLA shape set;
- **A/B**: continuous batching vs the naive one-request-at-a-time
  decode baseline (slots=1 engine, same params) on tokens/sec, plus
  an optional cache-free re-prefill-per-token baseline
  (``--with-reprefill``).

Prints ONE JSON line and
exits nonzero when any correctness gate fails. CPU host-device rig:
absolute numbers are hardware-dependent; the correctness gates and the
continuous-vs-naive ratio are the committed signal (GEN_r01.json,
BENCH_NOTES.md).
"""

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def pct(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


def build_engine(args, slots):
    from analytics_zoo_tpu.serving.generation.engine import DecodeEngine
    from analytics_zoo_tpu.serving.generation.model import (
        GenModelConfig, TinyGenLM)

    cfg = GenModelConfig(vocab=64, dim=32, heads=2, head_dim=16,
                         layers=2, max_len=args.max_len,
                         seed=args.seed)
    return DecodeEngine(TinyGenLM(cfg), num_slots=slots,
                        page_size=args.page_size,
                        max_len=args.max_len)


def make_prompts(args):
    rng = np.random.RandomState(args.seed)
    return [rng.randint(0, 64, rng.randint(2, args.prompt_max))
            .astype(np.int32) for _ in range(args.prompt_pool)]


def solo_expected(params, prompts, args):
    """Ground truth per pool prompt: solo decode on a fresh 1-slot
    engine sharing the same params (the 'solo decode' of the
    acceptance criteria)."""
    eng = build_engine(args, slots=1)
    eng.params = params
    eng.warm_up()
    out = []
    for p in prompts:
        slot, t0 = eng.admit(p, args.max_tokens)
        toks = [t0]
        while len(toks) < args.max_tokens:
            toks.append(dict(eng.step())[slot])
        eng.release(slot)
        out.append(toks)
    return out


def run_continuous(args, engine, prompts):
    """The soak: ``--requests`` streams with overlapping lifetimes
    through one GenerationWorker; returns (records, elapsed_s)."""
    from analytics_zoo_tpu.serving.protocol import ERROR_KEY, STREAM_KEY
    from analytics_zoo_tpu.serving.queues import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.generation.worker import (
        GenerationWorker)

    in_q = InputQueue(backend="memory")
    out_q = OutputQueue(backend="memory")
    worker = GenerationWorker(engine, in_q, out_q)
    recs = {}
    done = threading.Event()
    lock = threading.Lock()
    finished = [0]

    def collector():
        while not done.is_set() or finished[0] < args.requests:
            item = out_q.dequeue(timeout=0.2)
            if item is None:
                if done.is_set() and finished[0] >= args.requests:
                    return
                continue
            now = time.perf_counter()
            uri, tensors = item
            rec = recs.get(uri)
            if rec is None:
                continue
            rec["chunk_t"].append(now)
            rec["seqs"].append(int(np.asarray(
                tensors[STREAM_KEY]).reshape(())))
            if ERROR_KEY in tensors:
                rec["error"] = str(np.asarray(
                    tensors[ERROR_KEY]).reshape(()))
                rec["terminal"] = rec.get("terminal", 0) + 1
                with lock:
                    finished[0] += 1
                continue
            if "token" in tensors:
                rec["toks"].extend(int(t) for t in np.asarray(
                    tensors["token"]).reshape(-1))
            if "finish_reason" in tensors:
                rec["terminal"] = rec.get("terminal", 0) + 1
                with lock:
                    finished[0] += 1

    col = threading.Thread(target=collector, daemon=True)
    col.start()
    worker.start()
    t_start = time.perf_counter()
    submitted = 0
    try:
        while finished[0] < args.requests:
            with lock:
                outstanding = submitted - finished[0]
            if submitted < args.requests and \
                    outstanding < args.concurrency:
                pool_i = submitted % len(prompts)
                uri = f"r{submitted}-p{pool_i}"
                recs[uri] = {"pool": pool_i, "toks": [], "seqs": [],
                             "chunk_t": [],
                             "enq_t": time.perf_counter()}
                in_q.enqueue_generation(uri, prompts[pool_i],
                                        max_tokens=args.max_tokens)
                submitted += 1
                continue
            time.sleep(0.001)
        elapsed = time.perf_counter() - t_start
    finally:
        done.set()
        col.join(10.0)
        worker.stop()
    return recs, elapsed


def run_naive_sequential(args, params, prompts, n):
    """Baseline: one-request-at-a-time decode (slots=1 engine, KV
    cache but zero batching) over the same workload shape."""
    eng = build_engine(args, slots=1)
    eng.params = params
    eng.warm_up()
    t0 = time.perf_counter()
    toks = 0
    for i in range(n):
        p = prompts[i % len(prompts)]
        slot, _ = eng.admit(p, args.max_tokens)
        produced = 1
        while produced < args.max_tokens:
            eng.step()
            produced += 1
        eng.release(slot)
        toks += produced
    return toks / (time.perf_counter() - t0)


def run_naive_reprefill(args, params, prompts, n):
    """Cache-free baseline: re-run the full prefix forward per token
    (eager; what serving generation through the predict path would
    amount to)."""
    from analytics_zoo_tpu.serving.generation.model import (
        GenModelConfig, TinyGenLM)

    cfg = GenModelConfig(vocab=64, dim=32, heads=2, head_dim=16,
                         layers=2, max_len=args.max_len,
                         seed=args.seed)
    lm = TinyGenLM(cfg)
    t0 = time.perf_counter()
    toks = 0
    for i in range(n):
        out = lm.reference_generate(params, prompts[i % len(prompts)],
                                    args.max_tokens)
        toks += len(out)
    return toks / (time.perf_counter() - t0)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--requests", type=int, default=500)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prompt-pool", type=int, default=32)
    ap.add_argument("--prompt-max", type=int, default=24)
    ap.add_argument("--naive-requests", type=int, default=40,
                    help="requests for the sequential baseline "
                         "(tokens/sec is per-request stable, so a "
                         "subset suffices)")
    ap.add_argument("--with-reprefill", action="store_true",
                    help="also run the cache-free re-prefill baseline")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (60 requests, concurrency 4)")
    args = ap.parse_args()
    if args.smoke:
        args.requests = 60
        args.concurrency = 4
        args.naive_requests = 10
        args.prompt_pool = 12
    assert args.prompt_max + args.max_tokens <= args.max_len

    from analytics_zoo_tpu.obs.events import get_event_log

    prompts = make_prompts(args)
    engine = build_engine(args, slots=args.slots)
    engine.warm_up()
    expected = solo_expected(engine.params, prompts, args)
    log = get_event_log()
    live_before = len([
        e for e in log.tail(100000, type="compile")
        if e["fields"]["fn"].startswith("generation.")
        and not e["fields"]["warm"]])

    recs, elapsed = run_continuous(args, engine, prompts)

    # ---------------------------------------------------- verdicts --
    exact = exactly_once = True
    unanswered = errors = 0
    ttft_ms, intertoken_ms = [], []
    for uri, rec in recs.items():
        if not rec.get("terminal"):
            unanswered += 1
            exactly_once = False
            continue
        if rec.get("terminal", 0) != 1:
            exactly_once = False
        if "error" in rec:
            errors += 1
            exact = False
            continue
        data_seqs = [s for s in rec["seqs"] if s >= 0]
        if data_seqs != list(range(len(data_seqs))):
            exactly_once = False
        if rec["toks"] != expected[rec["pool"]]:
            exact = False
        if rec["chunk_t"]:
            ttft_ms.append((rec["chunk_t"][0] - rec["enq_t"]) * 1e3)
            gaps = np.diff(rec["chunk_t"])
            intertoken_ms.extend(float(g) * 1e3 for g in gaps)
    total_tokens = sum(len(r["toks"]) for r in recs.values())
    cont_tps = total_tokens / elapsed if elapsed else 0.0

    storms = [e for e in log.tail(100000, type="recompile_storm")
              if e["subsystem"] == "generation"]
    live_after = len([
        e for e in log.tail(100000, type="compile")
        if e["fields"]["fn"].startswith("generation.")
        and not e["fields"]["warm"]])

    naive_tps = run_naive_sequential(args, engine.params, prompts,
                                     args.naive_requests)
    reprefill_tps = (run_naive_reprefill(
        args, engine.params, prompts,
        max(4, args.naive_requests // 4))
        if args.with_reprefill else None)

    ok = (exact and exactly_once and unanswered == 0 and errors == 0
          and not storms and live_after == live_before
          and cont_tps > naive_tps)
    line = {
        "mode": "perf_generation",
        "requests": args.requests,
        "concurrency": args.concurrency,
        "max_tokens": args.max_tokens,
        "slots": args.slots,
        "elapsed_s": round(elapsed, 3),
        "tokens_total": total_tokens,
        "tokens_per_s": round(cont_tps, 2),
        "ttft_ms": {"p50": round(pct(ttft_ms, 50), 2),
                    "p99": round(pct(ttft_ms, 99), 2)},
        "intertoken_ms": {"p50": round(pct(intertoken_ms, 50), 3),
                          "p99": round(pct(intertoken_ms, 99), 3)},
        "exact": exact,
        "exactly_once": exactly_once,
        "unanswered": unanswered,
        "errors": errors,
        "storms_after_warmup": len(storms),
        "live_compiles_after_warmup": live_after - live_before,
        "ab": {
            "continuous_tps": round(cont_tps, 2),
            "naive_sequential_tps": round(naive_tps, 2),
            "speedup": round(cont_tps / naive_tps, 2)
            if naive_tps else None,
            "naive_requests": args.naive_requests,
            "reprefill_tps": (round(reprefill_tps, 2)
                              if reprefill_tps is not None else None),
        },
        "seed": args.seed,
        "ok": ok,
    }
    print(json.dumps(line))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
