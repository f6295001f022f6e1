#!/usr/bin/env python
"""Chip smoke: drive the train, serve and generate paths once on the
attached TPU, through the entry points a user would call, and check
what comes out. The quickest proof that the system still starts on
the chip -- it claims no speed.

    python chip_smoke.py            # one chip, every default phase
    python chip_smoke.py --chips 4  # ONLY the four-chip mesh check
    python chip_smoke.py --fleet    # ONLY the one-replica fleet check

Default phases, all in THIS process (one process per chip -- the
device line below comes from the process that held it):

- device    platform / kind / count, ``memory_stats()`` present
- kernels   ``ops.dot_product_attention`` at L1024/L2048, d64, bf16,
            causal and not, with and without ``key_padding_mask``,
            forward and grad, against ``reference_attention`` in f32;
            the lowered text must hold a ``tpu_custom_call``
- train     ``BERTSQuAD`` at its published width, L384, batch 32, two
            passes over the same 16 seeded batches through ``model.fit``
- serve     ResNet-18 ``ImageClassifier`` saved and served through
            ``serving.launcher.launch``, JPEG
            requests against a direct ``InferenceModel.predict``
- generate  the ``generation:`` plane of the same launcher, streamed
            tokens against the model's teacher-forced full forward
            (``TinyGenLM`` -- a TOY width, named as such below)

Every phase prints one JSON line (wall seconds split into compile and
run, compile requests and persistent-cache hits, its own results).
Any failed check raises: the run ends non-zero and never prints the
result line. It refuses to run at all unless
``jax.devices()[0].platform == "tpu"``. The last stdout line of a
good run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

import argparse
import io
import json
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# bf16 tolerances, stated once. Attention: max error over max |ref|
# (bf16 has 8 mantissa bits; the kernels round p and the output to
# bf16). Serving: the same bf16 ResNet-18 at another batch bucket may
# fuse and round differently. Mesh: bf16 reductions in another order,
# compounding over optimizer steps.
ATTN_FWD_TOL = 2e-2
ATTN_GRAD_TOL = 4e-2
SERVE_TOL = 5e-2
MESH_LOSS_TOL = 2e-2

# the launcher's documented builtin LM (serving/launcher.py docstring):
# a toy, here only because this plane had never touched a chip
TOY_GEN_MODEL = {"vocab": 64, "dim": 32, "heads": 2, "head_dim": 16,
                 "layers": 2, "seed": 0}


class SmokeFailure(RuntimeError):
    """A phase's own check failed."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


class CompileWatch:
    """Process-wide XLA compile accounting from ``jax.monitoring``:
    compile requests (each one is a backend compile OR a persistent-
    cache read), their seconds, and cache hits. Serving threads
    compile too, hence the lock."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._c = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self._BACKEND:
            with self._lock:
                self._c["compiles"] += 1
                self._c["compile_s"] += duration

    def _on_event(self, event, **_):
        if event == self._HIT:
            with self._lock:
                self._c["cache_hits"] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._c)

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}


def run_phase(name: str, watch: CompileWatch, fn, **kwargs) -> dict:
    """Run one phase (its checks raise), print its JSON line:
    ``compile_s`` is XLA compile (or cache read) time, ``run_s`` the
    rest of the wall time (tracing, host set-up, device work)."""
    before = watch.snapshot()
    t0 = time.perf_counter()
    result = fn(watch, **kwargs)
    wall = time.perf_counter() - t0
    d = watch.since(before)
    line = {"phase": name, "ok": True, "wall_s": round(wall, 2),
            "compile_s": round(d["compile_s"], 2),
            "run_s": round(wall - d["compile_s"], 2),
            "compiles": d["compiles"], "cache_hits": d["cache_hits"]}
    line.update(result)
    print(json.dumps(line), flush=True)
    return line


def device_record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_hbm_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats else None


# ------------------------------------------------------------------ #
# device                                                             #
# ------------------------------------------------------------------ #
def phase_device(watch) -> dict:
    import jax

    stats = jax.devices()[0].memory_stats()
    check(stats and stats.get("bytes_limit"),
          "device reports no memory_stats()/bytes_limit")
    return {**device_record(), "hbm_bytes_limit": int(stats["bytes_limit"])}


# ------------------------------------------------------------------ #
# kernels                                                            #
# ------------------------------------------------------------------ #
def phase_kernels(watch, seqs=(1024, 2048), batch=2, heads=12,
                  head_dim=64, expect_kernel=True) -> dict:
    """The public dispatcher on device arrays. ``expect_kernel`` is
    what makes this a chip check: the lowered text must hold a
    ``tpu_custom_call`` (compiled kernel -- not the interpreter, not
    the einsum path); the CPU rehearsal passes False."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.ops import (dot_product_attention,
                                       reference_attention)

    def rel_err(got, ref):
        got = np.asarray(got.astype(jnp.float32))
        ref = np.asarray(ref)
        check(np.all(np.isfinite(got)), "non-finite attention output")
        return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))

    cases = []
    worst_fwd = worst_grad = 0.0
    rng = np.random.RandomState(0)
    for l in seqs:
        q, k, v, ct = (jnp.asarray(rng.randn(batch, heads, l, head_dim),
                                   jnp.bfloat16) for _ in range(4))
        # padding mask: row b keeps its first l - b*l/4 tokens. The
        # stock kernel's segment ids also fence PADDED queries off the
        # real keys (the reference masks keys only), so padded query
        # rows are excluded from the comparison and carry no cotangent
        keep = l - (np.arange(batch) * (l // 4))
        pad = (np.arange(l)[None, :] < keep[:, None]).astype(np.int32)
        for causal in (False, True):
            for masked in (False, True):
                kpm = jnp.asarray(pad) if masked else None
                rows = (jnp.asarray(pad, jnp.float32)[:, None, :, None]
                        if masked else jnp.ones((), jnp.float32))

                # everything below runs inside this iteration, so the
                # closures see this case's kpm / causal / rows / ct
                def fwd(q, k, v):
                    return dot_product_attention(
                        q, k, v, key_padding_mask=kpm, causal=causal)

                def ref(q, k, v):
                    with jax.default_matmul_precision("highest"):
                        return reference_attention(
                            q, k, v, causal=causal,
                            mask=(None if kpm is None else
                                  kpm[:, None, None, :]))

                def loss(attn):
                    return lambda q, k, v: jnp.sum(
                        attn(q, k, v).astype(jnp.float32) * rows
                        * ct.astype(jnp.float32))

                f_fwd = jax.jit(fwd)
                f_grad = jax.jit(jax.grad(loss(fwd), argnums=(0, 1, 2)))
                n_calls = (f_fwd.lower(q, k, v).as_text()
                           .count("tpu_custom_call"),
                           f_grad.lower(q, k, v).as_text()
                           .count("tpu_custom_call"))
                tag = f"L{l}{'_causal' if causal else ''}" \
                      f"{'_padmask' if masked else ''}"
                if expect_kernel:
                    check(min(n_calls) > 0,
                          f"{tag}: no tpu_custom_call in the lowered "
                          f"text {n_calls}: the dispatcher did not "
                          "reach a compiled kernel")
                q32, k32, v32 = (t.astype(jnp.float32)
                                 for t in (q, k, v))
                e_fwd = rel_err(f_fwd(q, k, v) * rows,
                                jax.jit(ref)(q32, k32, v32) * rows)
                g_ref = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(
                    q32, k32, v32)
                e_grad = max(rel_err(g, r) for g, r in
                             zip(f_grad(q, k, v), g_ref))
                check(e_fwd <= ATTN_FWD_TOL,
                      f"{tag}: forward error {e_fwd:.4f} > "
                      f"{ATTN_FWD_TOL}")
                check(e_grad <= ATTN_GRAD_TOL,
                      f"{tag}: grad error {e_grad:.4f} > "
                      f"{ATTN_GRAD_TOL}")
                worst_fwd = max(worst_fwd, e_fwd)
                worst_grad = max(worst_grad, e_grad)
                cases.append({"case": tag, "custom_calls": n_calls,
                              "fwd_err": round(e_fwd, 5),
                              "grad_err": round(e_grad, 5)})
    return {"shape": [batch, heads, "L", head_dim], "dtype": "bfloat16",
            "tolerance": {"fwd": ATTN_FWD_TOL, "grad": ATTN_GRAD_TOL},
            "worst_fwd_err": round(worst_fwd, 5),
            "worst_grad_err": round(worst_grad, 5), "cases": cases}


# ------------------------------------------------------------------ #
# train                                                              #
# ------------------------------------------------------------------ #
def squad_batches(n: int, seq: int, vocab: int, seed: int = 0):
    """Seeded synthetic SQuAD: random token ids, answer spans inside a
    narrow window (so a position prior is learnable in a few steps)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    x = {"input_ids": rng.randint(0, vocab, (n, seq)).astype(np.int32)}
    start = rng.randint(seq // 8, seq // 4, n)
    y = np.stack([start, start + rng.randint(0, seq // 16 + 1, n)],
                 axis=1).astype(np.int32)
    return x, y


def phase_train(watch, batch=32, seq=384, steps=16, vocab=30522,
                **model_kwargs) -> dict:
    """BERT-base SQuAD fine-tune through ``model.fit``; ``model_kwargs``
    shrink the encoder for the CPU rehearsal only."""
    import numpy as np

    from analytics_zoo_tpu.learn.optim import AdamWeightDecay
    from analytics_zoo_tpu.models.text.bert_squad import BERTSQuAD
    from analytics_zoo_tpu.obs.events import get_event_log

    x, y = squad_batches(batch * steps, seq, vocab)
    model = BERTSQuAD(vocab=vocab, dtype="bfloat16", **model_kwargs)
    # the reference's own BERT optimizer at its fine-tune rate
    model.compile(optimizer=AdamWeightDecay(lr=1e-4))
    def count_step_compiles():
        return sum(1 for e in get_event_log().tail(type="compile")
                   if e["fields"].get("fn") == "estimator.train_step")

    compiled_before = count_step_compiles()
    t0 = time.perf_counter()
    first = model.fit((x, y), batch_size=batch, epochs=1)[0]
    t1 = time.perf_counter()
    before = watch.snapshot()
    second = model.fit((x, y), batch_size=batch, epochs=2)[0]
    t2 = time.perf_counter()
    second_pass = watch.since(before)
    train_step_compiles = count_step_compiles() - compiled_before
    check(np.isfinite(first["loss"]) and np.isfinite(second["loss"]),
          f"non-finite loss: {first['loss']}, {second['loss']}")
    check(second["loss"] < first["loss"],
          f"second pass mean loss {second['loss']:.4f} not below the "
          f"first's {first['loss']:.4f}")
    # the train step compiled exactly once (at the first step) and the
    # whole second pass asked XLA for nothing
    check(train_step_compiles == 1,
          f"train step compiled {train_step_compiles} times")
    check(second_pass["compiles"] == 0,
          f"{second_pass['compiles']} compile requests during the "
          "second pass")
    c = model._config
    return {"model": "BERTSQuAD", "hidden": c["hidden_size"],
            "layers": c["n_block"], "heads": c["n_head"],
            "ffn": c["intermediate_size"], "vocab": vocab,
            "batch": batch, "seq": seq, "steps_per_pass": steps,
            # a pass's mean loss is finite only if every step's was
            "loss_pass1": round(first["loss"], 4),
            "loss_pass2": round(second["loss"], 4),
            "pass1_s": round(t1 - t0, 2), "pass2_s": round(t2 - t1, 2),
            "train_step_compiles": train_step_compiles,
            "compiles_in_pass2": second_pass["compiles"],
            "peak_hbm_bytes": _peak_hbm_bytes()}


# ------------------------------------------------------------------ #
# serve                                                              #
# ------------------------------------------------------------------ #
def _jpeg_requests(n: int, size: int, seed: int = 0):
    import numpy as np
    from PIL import Image

    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        buf = io.BytesIO()
        Image.fromarray((rng.rand(size, size, 3) * 255).astype(np.uint8)
                        ).save(buf, format="JPEG", quality=90)
        out.append(np.frombuffer(buf.getvalue(), np.uint8))
    return out


def phase_serve(watch, n_requests=192, batch=128, image_size=224,
                backbone="resnet18", class_num=1000) -> dict:
    import numpy as np
    from PIL import Image

    from analytics_zoo_tpu.inference.inference_model import (
        InferenceModel)
    from analytics_zoo_tpu.models.image.classifier import (
        ImageClassifier)
    from analytics_zoo_tpu.serving.launcher import launch
    from analytics_zoo_tpu.serving.protocol import ERROR_KEY

    jpegs = _jpeg_requests(n_requests, image_size)
    with tempfile.TemporaryDirectory() as tmp:
        mdir = os.path.join(tmp, "model")
        ImageClassifier(class_num=class_num, backbone=backbone,
                        image_size=image_size,
                        dtype="bfloat16").save_model(mdir)
        t0 = time.perf_counter()
        app = launch({
            "model": {"path": mdir},
            # decoded JPEGs arrive as uint8 (normalized on device);
            # growth pinned to the warmed ladder's top
            "params": {"batch_size": batch, "max_batch_size": batch,
                       "timeout_ms": 2.0,
                       "warm_example": np.zeros(
                           (1, image_size, image_size, 3), np.uint8)},
            "http": {"enabled": False},
        })
        warm_s = time.perf_counter() - t0
        try:
            before = watch.snapshot()
            t0 = time.perf_counter()
            for i, jpeg in enumerate(jpegs):
                check(app.input_queue.enqueue(f"req-{i}", input=jpeg),
                      f"request {i} refused at the input queue")
            replies = {}
            deadline = time.perf_counter() + 120.0
            settle = None
            while time.perf_counter() < (settle or deadline):
                for uri, tensors in app.output_queue.dequeue_all():
                    replies.setdefault(uri, []).append(tensors)
                if settle is None and len(replies) == n_requests:
                    # keep listening briefly: a duplicate reply would
                    # arrive after the first complete set
                    settle = time.perf_counter() + 0.5
                time.sleep(0.005)
            served_s = time.perf_counter() - t0
            live = watch.since(before)
        finally:
            app.stop()
        check(len(replies) == n_requests,
              f"{len(replies)}/{n_requests} requests answered")
        check(all(len(r) == 1 for r in replies.values()),
              "a request was answered more than once")
        errors = [u for u, r in replies.items() if ERROR_KEY in r[0]]
        check(not errors, f"error replies: {errors[:5]}")
        check(live["compiles"] == 0,
              f"{live['compiles']} live compiles after warm-up")

        images = np.stack([
            np.asarray(Image.open(io.BytesIO(j.tobytes())).convert("RGB"),
                       np.uint8) for j in jpegs])
        direct = InferenceModel().load_zoo(mdir)
        chunk = min(64, n_requests)
        ref = np.concatenate([
            np.asarray(direct.predict(images[i:i + chunk]), np.float32)
            for i in range(0, n_requests, chunk)])
    got = np.stack([np.asarray(replies[f"req-{i}"][0]["output"],
                               np.float32) for i in range(n_requests)])
    check(got.shape == (n_requests, class_num),
          f"served output shape {got.shape}")
    check(np.all(np.isfinite(got)), "non-finite served output")
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    check(err <= SERVE_TOL,
          f"served vs direct predict error {err:.4f} > {SERVE_TOL}")
    return {"model": f"ImageClassifier/{backbone}", "dtype": "bfloat16",
            "requests": n_requests,
            "answered_once": n_requests, "error_replies": 0,
            "max_batch": batch, "warm_up_s": round(warm_s, 2),
            "served_s": round(served_s, 2),
            "live_compiles": live["compiles"],
            "tolerance": SERVE_TOL, "max_rel_err": round(err, 5)}


# ------------------------------------------------------------------ #
# generate                                                           #
# ------------------------------------------------------------------ #
def _collect_streams(out_q, uris, timeout_s: float = 120.0) -> dict:
    """Chunk streams off an OutputQueue: ``{uri: {"toks", "seqs",
    "reason"}}``; an error chunk or a missing terminal raises."""
    import numpy as np

    from analytics_zoo_tpu.serving.protocol import ERROR_KEY, STREAM_KEY

    got = {u: {"toks": [], "seqs": [], "reason": None} for u in uris}
    open_ = set(uris)
    deadline = time.perf_counter() + timeout_s
    while open_ and time.perf_counter() < deadline:
        item = out_q.dequeue(timeout=0.2)
        if item is None:
            continue
        uri, tensors = item
        check(uri in got, f"chunk for unknown stream {uri}")
        check(ERROR_KEY not in tensors,
              f"{uri}: error chunk {tensors.get(ERROR_KEY)}")
        rec = got[uri]
        rec["seqs"].append(int(np.asarray(tensors[STREAM_KEY])))
        if "token" in tensors:
            rec["toks"].extend(
                int(t) for t in np.asarray(tensors["token"]).reshape(-1))
        if "finish_reason" in tensors:
            rec["reason"] = str(np.asarray(tensors["finish_reason"]))
            open_.discard(uri)
    check(not open_, f"streams without a terminal chunk: {sorted(open_)}")
    return got


def teacher_forced_mismatches(model, params, prompt, tokens,
                              pad_to: int) -> int:
    """Token-exactness against the model's FULL forward: one causal
    pass over prompt+generated (padded to a fixed length -- padding
    sits after every position read, so causality hides it); greedy
    token i must be the argmax at position len(prompt)-1+i."""
    import jax
    import numpy as np

    seq = np.zeros(pad_to, np.int32)
    n = len(prompt) + len(tokens)
    seq[:n] = np.concatenate([prompt, tokens])
    logits, _, _ = jax.jit(model.prefill)(params, seq[None])
    want = np.asarray(logits[0]).argmax(-1)[len(prompt) - 1:n - 1]
    return int(np.sum(want != np.asarray(tokens)))


def phase_generate(watch, n_prompts=6, max_tokens=32, max_len=128
                   ) -> dict:
    import numpy as np

    from analytics_zoo_tpu.serving.launcher import launch

    rng = np.random.RandomState(0)
    prompts = {f"gen-{i}": rng.randint(
        0, TOY_GEN_MODEL["vocab"], rng.randint(3, 24)).astype(np.int32)
        for i in range(n_prompts)}
    t0 = time.perf_counter()
    app = launch({
        "generation": {"model": dict(TOY_GEN_MODEL), "slots": 4,
                       "page_size": 16, "max_len": max_len},
        "http": {"enabled": False},
    })
    warm_s = time.perf_counter() - t0
    try:
        before = watch.snapshot()
        t0 = time.perf_counter()
        for uri, prompt in prompts.items():
            check(app.gen_input_queue.enqueue_generation(
                uri, prompt, max_tokens=max_tokens),
                f"{uri} refused at the generation queue")
        streams = _collect_streams(app.output_queue, list(prompts))
        streamed_s = time.perf_counter() - t0
        live = watch.since(before)
        engine = app.gen_worker.engine
        model, params = engine.model, engine.params
    finally:
        app.stop()
    mismatches = 0
    for uri, rec in streams.items():
        check(rec["seqs"] == list(range(len(rec["seqs"]))),
              f"{uri}: chunk seqs not contiguous: {rec['seqs']}")
        check(rec["reason"] == "length" and
              len(rec["toks"]) == max_tokens,
              f"{uri}: {len(rec['toks'])} tokens, finish "
              f"{rec['reason']!r}")
        mismatches += teacher_forced_mismatches(
            model, params, prompts[uri], rec["toks"], max_len)
    check(mismatches == 0,
          f"{mismatches} streamed tokens differ from the full forward")
    check(live["compiles"] == 0,
          f"{live['compiles']} live compiles after warm-up")
    return {"model": "TinyGenLM (TOY width, not a supported "
                     "architecture)", "config": TOY_GEN_MODEL,
            "streams": n_prompts, "tokens_per_stream": max_tokens,
            "token_mismatches": mismatches,
            "warm_up_s": round(warm_s, 2),
            "streamed_s": round(streamed_s, 2),
            "live_compiles": live["compiles"]}


# ------------------------------------------------------------------ #
# --chips 4: one-device vs data-4 vs data-2 x model-2                #
# ------------------------------------------------------------------ #
def phase_mesh(watch, batch=32, seq=384, steps=3, vocab=30522,
               n_devices=4, **model_kwargs) -> dict:
    """BERT-base ``Estimator.fit`` under three layouts from one
    process, same seed and global batch. One step per epoch, so the
    per-epoch history IS the per-step loss; dropout is off so the
    layouts are comparable (the TPU's hardware PRNG draws differently
    under each sharding)."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.learn.optim import AdamWeightDecay
    from analytics_zoo_tpu.models.text.bert_squad import (
        BERTForSQuAD, squad_span_loss)
    from analytics_zoo_tpu.parallel import create_mesh
    from analytics_zoo_tpu.parallel.recipes import transformer_tp_spec
    from analytics_zoo_tpu.parallel.sharding import (replicated,
                                                     shard_batch)

    devs = jax.devices()
    check(len(devs) >= n_devices,
          f"need {n_devices} devices, found {len(devs)}")
    x, y = squad_batches(batch, seq, vocab)
    layouts = {
        "one_device": ({"data": 1}, devs[:1], None),
        "data4": ({"data": n_devices}, devs[:n_devices], None),
        "data2_model2": ({"data": n_devices // 2, "model": 2},
                         devs[:n_devices], transformer_tp_spec()),
    }
    out = {}
    for name, (shape, mesh_devs, spec_fn) in layouts.items():
        mesh = create_mesh(shape, devices=mesh_devs)
        module = BERTForSQuAD(vocab=vocab, hidden_dropout=0.0,
                              dtype=jnp.bfloat16, **model_kwargs)
        est = Estimator(module, loss=squad_span_loss,
                        optimizer=AdamWeightDecay(lr=1e-4), mesh=mesh,
                        param_spec_fn=spec_fn, seed=0)
        losses = [h["loss"] for h in
                  est.fit((x, y), batch_size=batch, epochs=steps)]
        # the program that just ran (same argument placement, so the
        # persistent cache answers instead of a second compile)
        xb, yb = shard_batch((x, y), mesh)
        hlo = est._build_train_step().__wrapped__.lower(
            est.variables, est.opt_state,
            jax.device_put(jnp.zeros((), jnp.float32), replicated(mesh)),
            xb, yb, est._rng).compile().as_text()
        params = jax.tree_util.tree_leaves(est.variables["params"])
        out[name] = {
            "mesh": shape, "losses": [round(v, 5) for v in losses],
            "all_reduces": hlo.count(" all-reduce("),
            "batch_shard_devices": len(
                {s.device for s in xb["input_ids"].addressable_shards}),
            "batch_shard_shape": list(
                xb["input_ids"].addressable_shards[0].data.shape),
            "param_devices": max(
                len({s.device for s in p.addressable_shards})
                for p in params),
            "sharded_params": sum(
                1 for p in params
                if p.addressable_shards[0].data.shape != p.shape),
        }
    base = out["one_device"]
    check(base["all_reduces"] == 0,
          "one-device step holds an all-reduce")
    worst = 0.0
    for name in ("data4", "data2_model2"):
        rec = out[name]
        check(rec["all_reduces"] > 0, f"{name}: no all-reduce in the "
                                      "compiled step")
        check(rec["batch_shard_devices"] == n_devices,
              f"{name}: batch shards on {rec['batch_shard_devices']} "
              "devices")
        check(rec["param_devices"] == n_devices,
              f"{name}: parameters on {rec['param_devices']} devices")
        for a, b in zip(rec["losses"], base["losses"]):
            worst = max(worst, abs(a - b) / abs(b))
    check(out["data4"]["sharded_params"] == 0,
          "data4 sharded a parameter")
    check(out["data2_model2"]["sharded_params"] > 0,
          "data2_model2 sharded no parameter")
    check(worst <= MESH_LOSS_TOL,
          f"per-step losses differ by {worst:.4f} > {MESH_LOSS_TOL}")
    return {"model": "BERTForSQuAD", "batch": batch, "seq": seq,
            "steps": steps, "dropout": 0.0,
            "tolerance": MESH_LOSS_TOL,
            "worst_rel_loss_diff": round(worst, 5), "layouts": out}


# ------------------------------------------------------------------ #
# --fleet: a JAX-free controller, ONE replica on the chip            #
# ------------------------------------------------------------------ #
def phase_fleet(expect_backend="tpu", max_tokens=16) -> dict:
    """A ``FleetController`` in a process that has NOT touched the
    backend brings up one replica; the replica (its own process)
    takes the chip, serves one ``/generate`` through the router, and
    gives the chip back when it is drained and reaped."""
    import urllib.request

    from analytics_zoo_tpu.common.context import backend_initialized
    from analytics_zoo_tpu.serving.fleet import FleetController

    check(not backend_initialized(),
          "this process already holds a backend")
    prompt = [3, 7, 1, 9, 2]
    with tempfile.TemporaryDirectory() as work:
        fc = FleetController(
            {"generation": {"model": dict(TOY_GEN_MODEL), "slots": 4,
                            "page_size": 16, "max_len": 128}},
            replicas=1, work_dir=work)
        fc.start()
        try:
            check(fc.wait_healthy(1, timeout_s=600.0),
                  f"replica never became healthy: {fc.replica_states()}")
            req = urllib.request.Request(
                fc.router.address + "/generate",
                data=json.dumps({"prompt": prompt,
                                 "max_tokens": max_tokens}).encode(),
                headers={"Content-Type": "application/json"})
            events = []
            with urllib.request.urlopen(req, timeout=120) as resp:
                for line in resp:
                    if line.startswith(b"data: "):
                        events.append(json.loads(line[6:]))
            tokens = [t for e in events for t in e.get("token", [])]
            check(len(tokens) == max_tokens and not any(
                "error" in e for e in events),
                f"bad /generate stream: {events[-1:]}")
            rep = fc.pick_replica()
            with urllib.request.urlopen(rep.address + "/debug/vars",
                                        timeout=30) as resp:
                backend = json.loads(resp.read())["build"]["backend"]
            check(backend == expect_backend,
                  f"replica reports backend {backend!r}, want "
                  f"{expect_backend!r}")
            # the router/front door stayed off the device throughout
            check(not backend_initialized(),
                  "serving a request initialized a backend in the "
                  "controller process")
        finally:
            fc.stop(drain=True)  # SIGTERM + reap: the chip is free
    return {"replicas": 1, "replica_backend": backend,
            "prompt": prompt, "tokens": tokens}


# ------------------------------------------------------------------ #
def require_tpu() -> None:
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: refusing to run: jax.devices()[0].platform is "
            f"{platform!r}, not 'tpu' (no result line is printed)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run ONLY the four-chip mesh check")
    ap.add_argument("--fleet", action="store_true",
                    help="run ONLY the one-replica fleet check")
    args = ap.parse_args(argv)

    if args.fleet:
        # before this process touches the backend: the replica must be
        # the first (and, while it lives, the only) holder of the chip
        t0 = time.perf_counter()
        fleet = phase_fleet()
        print(json.dumps({"phase": "fleet", "ok": True, "wall_s": round(
            time.perf_counter() - t0, 2), **fleet}), flush=True)
    require_tpu()
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.common.context import COMPILE_CACHE_DIR
    from analytics_zoo_tpu.serving.generation.model import (
        GenModelConfig, TinyGenLM)

    init_zoo_context()
    watch = CompileWatch()
    if args.fleet:
        # the chip came back: this process now holds it, and checks the
        # replica's tokens against the same seeded model's full forward
        model = TinyGenLM(GenModelConfig.from_dict(TOY_GEN_MODEL))
        bad = teacher_forced_mismatches(
            model, model.init_params(pos_len=128), fleet["prompt"],
            fleet["tokens"], 128)
        check(bad == 0, f"{bad} replica tokens differ from the full "
                        "forward")
    elif args.chips == 4:
        run_phase("mesh", watch, phase_mesh)
    else:
        run_phase("device", watch, phase_device)
        run_phase("kernels", watch, phase_kernels)
        run_phase("train", watch, phase_train)
        run_phase("serve", watch, phase_serve)
        run_phase("generate", watch, phase_generate)
    total = watch.snapshot()
    print(json.dumps({"phase": "total", "compile_s": round(
        total["compile_s"], 2), "compiles": total["compiles"],
        "cache_hits": total["cache_hits"],
        "cache_dir": (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or COMPILE_CACHE_DIR)}), flush=True)
    print(json.dumps({"ok": True, "device": device_record()}),
          flush=True)


if __name__ == "__main__":
    main()
