"""Flight recorder acceptance (ISSUE-3): structured event log, crash
postmortems, recompile-storm detection, and the /debug endpoints.

The two headline scenarios from the issue's acceptance criteria:

- a serving worker killed by an injected exception leaves a postmortem
  bundle containing the last-N events, a metrics-registry snapshot,
  and the in-flight request ids;
- one jitted fn driven through >= K distinct shapes raises a
  ``recompile_storm`` event and bumps
  ``zoo_obs_recompile_storms_total``.
"""

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.obs import events as ev
from analytics_zoo_tpu.obs.flight import (
    FlightRecorder, get_inflight)
from analytics_zoo_tpu.obs.metrics import get_registry


# ---------------------------------------------------------------- #
# event log                                                        #
# ---------------------------------------------------------------- #
class TestEventLog:
    def test_emit_and_tail(self):
        log = ev.EventLog(max_events=16)
        log.emit("compile", "inference", fn="f", wall_s=0.5)
        log.emit("worker_start", "serving")
        log.emit("compile", "learn", fn="g")
        assert len(log) == 3
        assert [e["type"] for e in log.tail()] == [
            "compile", "worker_start", "compile"]
        # seq is monotonic, ts present
        seqs = [e["seq"] for e in log.tail()]
        assert seqs == sorted(seqs) and len(set(seqs)) == 3
        assert all(e["ts"] > 0 for e in log.tail())

    def test_tail_filters_before_truncation(self):
        log = ev.EventLog(max_events=64)
        for i in range(10):
            log.emit("compile", "inference", i=i)
            log.emit("worker_start", "serving", i=i)
        compiles = log.tail(5, type="compile")
        assert len(compiles) == 5
        assert [e["fields"]["i"] for e in compiles] == [5, 6, 7, 8, 9]
        assert log.tail(subsystem="serving")[0]["type"] == \
            "worker_start"

    def test_ring_bounded(self):
        log = ev.EventLog(max_events=4)
        for i in range(10):
            log.emit("compile", "inference", i=i)
        assert len(log) == 4
        assert log.tail()[0]["fields"]["i"] == 6

    def test_tail_zero_and_negative_n(self):
        """tail(0) must be empty, not the whole ring (out[-0:] trap)."""
        log = ev.EventLog(max_events=8)
        log.emit("compile", "inference")
        log.emit("compile", "inference")
        assert log.tail(0) == []
        assert log.tail(-3) == []
        assert len(log.tail(1)) == 1

    def test_unknown_type_rejected(self):
        log = ev.EventLog(max_events=4)
        with pytest.raises(ValueError, match="not registered"):
            log.emit("made_up_event", "serving")
        with pytest.raises(ValueError, match="snake_case"):
            ev.check_event_type("BadCamelCase")

    def test_register_event_type(self):
        ev.register_event_type("compile", ev.EVENT_TYPES["compile"])
        with pytest.raises(ValueError, match="already registered"):
            ev.register_event_type("compile", "something else")
        with pytest.raises(ValueError, match="snake_case"):
            ev.register_event_type("Bad-Name", "x")

    def test_jsonl_render_coerces_unserializable(self):
        log = ev.EventLog(max_events=8)
        log.emit("compile", "inference",
                 shapes=((np.int64(8), 3), "float32"),
                 arr=np.arange(2), exc=ValueError("boom"))
        lines = log.to_jsonl().splitlines()
        assert len(lines) == 1
        rec = json.loads(lines[0])  # must parse back
        assert rec["type"] == "compile"
        assert rec["fields"]["shapes"] == [[8, 3], "float32"]

    def test_events_counter(self):
        fam = get_registry().get("zoo_obs_events_total")
        before = fam.labels(type="pipeline_abort").value
        ev.emit("pipeline_abort", "serving", dropped=1)
        assert fam.labels(type="pipeline_abort").value == before + 1


# ---------------------------------------------------------------- #
# recompile storms                                                 #
# ---------------------------------------------------------------- #
class TestRecompileStorm:
    def test_detector_warns_at_threshold(self):
        log = ev.EventLog(max_events=64)
        det = ev.RecompileDetector(window_s=60.0, threshold=3, log=log)
        assert not det.record_compile("fn", ((1,), "f32"), 0.01)
        assert not det.record_compile("fn", ((2,), "f32"), 0.01)
        assert det.record_compile("fn", ((3,), "f32"), 0.01)
        storms = log.tail(type="recompile_storm")
        assert len(storms) == 1
        f = storms[0]["fields"]
        assert f["fn"] == "fn" and f["distinct"] == 3
        # repeat shapes do not re-warn inside the window
        assert not det.record_compile("fn", ((3,), "f32"), 0.01)
        assert len(log.tail(type="recompile_storm")) == 1

    def test_detector_is_per_fn(self):
        log = ev.EventLog(max_events=64)
        det = ev.RecompileDetector(window_s=60.0, threshold=3, log=log)
        for i in range(2):
            det.record_compile("a", ((i,), "f32"))
            det.record_compile("b", ((i,), "f32"))
        assert log.tail(type="recompile_storm") == []

    def test_window_expiry(self):
        log = ev.EventLog(max_events=64)
        det = ev.RecompileDetector(window_s=0.05, threshold=2, log=log)
        det.record_compile("fn", ((1,), "f32"))
        time.sleep(0.1)  # first compile falls out of the window
        assert not det.record_compile("fn", ((2,), "f32"))

    def test_inference_model_storm_end_to_end(self):
        """Acceptance: one jitted fn through >= K distinct shapes ->
        recompile_storm event + counter increment (the InferenceModel
        bucket cache is the storm surface serving cares about)."""
        from analytics_zoo_tpu.inference.inference_model import (
            InferenceModel)

        det = ev.get_recompile_detector()
        det.reset()  # a clean window for this test's fn
        counter = get_registry().get("zoo_obs_recompile_storms_total")
        before = counter.value
        log = ev.get_event_log()
        first = len(log.tail(type="compile"))

        m = InferenceModel()
        m._apply_fn = lambda v, x: x * 2.0
        m.variables = {}
        k = det.threshold
        for d in range(1, k + 2):  # K+1 distinct feature widths
            out = m.predict(np.ones((1, d), np.float32))
            np.testing.assert_allclose(out, 2.0 * np.ones((1, d)))

        compiles = log.tail(type="compile")
        assert len(compiles) - first >= k + 1
        mine = [e for e in compiles
                if e["fields"]["fn"] == "inference.predict"]
        assert mine and mine[-1]["fields"]["wall_s"] > 0
        assert "float32" in mine[-1]["fields"]["shapes"]
        storms = [e for e in log.tail(type="recompile_storm")
                  if e["fields"]["fn"] == "inference.predict"]
        assert storms, "no recompile_storm event for inference.predict"
        assert counter.value >= before + 1

    def test_warm_up_compiles_do_not_count_as_storm(self):
        """warm_up() walks the whole bucket ladder (>= threshold
        distinct shapes in seconds) -- logged as warm compiles,
        excluded from the storm window; a healthy launch must not cry
        storm."""
        from analytics_zoo_tpu.inference.inference_model import (
            InferenceModel)

        det = ev.get_recompile_detector()
        det.reset()
        counter = get_registry().get("zoo_obs_recompile_storms_total")
        before = counter.value
        m = InferenceModel()
        m._apply_fn = lambda v, x: x * 3.0
        m.variables = {}
        ladder = tuple(2 ** i for i in range(det.threshold + 2))
        m.warm_up(np.ones((1, 4), np.float32), batch_sizes=ladder)
        assert counter.value == before
        warm = [e for e in ev.get_event_log().tail(type="compile")
                if e["fields"].get("warm")]
        assert len(warm) >= det.threshold

    def test_graph_model_warm_up_does_not_storm(self):
        """The warming() context must reach the graph executor's
        compile boundary too: a graph-backed model warmed over the
        ladder emits only warm compiles (for both graph.* and
        inference.predict fns) and no storm."""
        from analytics_zoo_tpu.inference.graph_executor import (
            GraphFunction, _Node, _make_tf_ops)
        from analytics_zoo_tpu.inference.inference_model import (
            InferenceModel)

        det = ev.get_recompile_detector()
        det.reset()
        counter = get_registry().get("zoo_obs_recompile_storms_total")
        before = counter.value
        gf = GraphFunction(
            [_Node("y", "Identity", [("x", 0)], {})], {}, ["x"],
            [("y", 0)], _make_tf_ops(), "tf")
        m = InferenceModel().load_graph(gf)
        ladder = tuple(2 ** i for i in range(det.threshold + 1))
        m.warm_up(np.ones((1, 3), np.float32), batch_sizes=ladder)
        assert counter.value == before
        fresh = [e for e in ev.get_event_log().tail(type="compile")
                 if e["fields"]["fn"].startswith("graph.")
                 and not e["fields"].get("warm")]
        assert not fresh, fresh

    def test_instrumented_jit_records_each_new_signature(self):
        """The cache-size fast path: a jitted fn wrapped by
        instrument_compiles records exactly one compile per new input
        signature and none for repeats."""
        import jax

        log = ev.get_event_log()
        fn = ev.instrument_compiles(jax.jit(lambda x: x * 2),
                                    "test.jit_probe",
                                    subsystem="learn")
        n0 = len([e for e in log.tail(type="compile")
                  if e["fields"]["fn"] == "test.jit_probe"])
        fn(np.ones(3, np.float32))
        fn(np.ones(3, np.float32))  # repeat: no new compile
        fn(np.ones(5, np.float32))  # new signature
        mine = [e for e in log.tail(type="compile")
                if e["fields"]["fn"] == "test.jit_probe"]
        assert len(mine) - n0 == 2
        assert all(e["fields"]["wall_s"] > 0 for e in mine)

    def test_warm_traffic_emits_no_compiles(self):
        """The negative: repeat shapes never touch the detector (the
        hot path's only cost is the existing bucket-cache lookup)."""
        from analytics_zoo_tpu.inference.inference_model import (
            InferenceModel)

        m = InferenceModel()
        m._apply_fn = lambda v, x: x + 1.0
        m.variables = {}
        m.predict(np.zeros((2, 3), np.float32))
        log = ev.get_event_log()
        n = len(log.tail(type="compile"))
        for _ in range(5):
            m.predict(np.zeros((2, 3), np.float32))
        assert len(log.tail(type="compile")) == n


# ---------------------------------------------------------------- #
# postmortems                                                      #
# ---------------------------------------------------------------- #
class TestPostmortem:
    def test_bundle_contents(self, tmp_path):
        get_inflight().add(["req-a", "req-b"])
        try:
            rec = FlightRecorder(out_dir=str(tmp_path), max_events=16)
            ev.emit("worker_start", "serving", marker="bundle-test")
            path = rec.write_postmortem(
                "unit_test", exc=ValueError("injected"))
            assert path and os.path.isdir(path)
            files = set(os.listdir(path))
            assert files >= {"manifest.json", "events.jsonl",
                             "metrics.json", "spans.json",
                             "inflight.json", "config.json"}
            with open(os.path.join(path, "manifest.json")) as f:
                manifest = json.load(f)
            assert manifest["reason"] == "unit_test"
            assert manifest["exception"]["type"] == "ValueError"
            assert manifest["exception"]["message"] == "injected"
            assert manifest["pid"] == os.getpid()
            with open(os.path.join(path, "events.jsonl")) as f:
                lines = [json.loads(ln) for ln in f if ln.strip()]
            assert len(lines) <= 16
            assert any(e.get("fields", {}).get("marker")
                       == "bundle-test" for e in lines)
            with open(os.path.join(path, "metrics.json")) as f:
                snap = json.load(f)
            assert "zoo_obs_recompile_storms_total" in snap
            with open(os.path.join(path, "inflight.json")) as f:
                inflight = json.load(f)
            assert {"req-a", "req-b"} <= set(inflight["request_ids"])
            with open(os.path.join(path, "config.json")) as f:
                cfg = json.load(f)
            assert "zoo.obs.postmortem.dir" in cfg
        finally:
            get_inflight().discard(["req-a", "req-b"])

    def test_install_uninstall_restores_hooks(self, tmp_path):
        prev_sys, prev_thread = sys.excepthook, threading.excepthook
        rec = FlightRecorder(out_dir=str(tmp_path))
        rec.install()
        try:
            assert getattr(sys.excepthook, "__self__", None) is rec
            assert getattr(threading.excepthook, "__self__",
                           None) is rec
            rec.install()  # idempotent
            assert rec._prev_excepthook is prev_sys
        finally:
            rec.uninstall()
        assert sys.excepthook is prev_sys
        assert threading.excepthook is prev_thread

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_worker_crash_writes_bundle(self, tmp_path):
        """Acceptance: kill a serving worker with an injected exception
        -> a postmortem bundle appears containing last-N events, a
        registry snapshot, and the in-flight request ids.

        The engine answers every dispatched batch before its driver
        thread unwinds, so the thread whose death loses requests is
        the finalize stage: a fatal fault there leaves req-crash
        dispatched and never answered."""
        from analytics_zoo_tpu.serving import chaos
        from analytics_zoo_tpu.serving.queues import (
            InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.worker import ServingWorker

        class SlowModel:
            def predict(self, x):
                return np.asarray(x, np.float32)

        rec = FlightRecorder(out_dir=str(tmp_path), max_events=64)
        rec.install()
        chaos.install(chaos.ChaosInjector(
            chaos.parse_spec("crash:finalize:at=1")))
        worker = None
        try:
            q = InputQueue()
            assert q.enqueue("req-crash", x=np.ones(3, np.float32))
            worker = ServingWorker(
                SlowModel(), q, OutputQueue(), batch_size=1,
                timeout_ms=1.0, pipeline_depth=4)
            worker.start()
            deadline = time.monotonic() + 10
            bundle = None
            while time.monotonic() < deadline:
                found = [d for d in os.listdir(tmp_path)
                         if d.startswith("postmortem-")]
                if found:
                    bundle = os.path.join(tmp_path, found[0])
                    # the manifest is written first; wait for the
                    # last file so reads below never race the dump
                    if os.path.exists(os.path.join(bundle,
                                                   "config.json")):
                        break
                time.sleep(0.05)
            assert bundle, "no postmortem bundle appeared"
            with open(os.path.join(bundle, "manifest.json")) as f:
                manifest = json.load(f)
            assert manifest["reason"] == "thread_exception"
            assert manifest["exception"]["type"] == "ChaosCrash"
            assert "injected crash at finalize" in \
                manifest["exception"]["message"]
            with open(os.path.join(bundle, "events.jsonl")) as f:
                events = [json.loads(ln) for ln in f if ln.strip()]
            types = [e["type"] for e in events]
            assert "worker_start" in types
            crashes = [e for e in events
                       if e["type"] == "uncaught_exception"]
            assert crashes and crashes[-1]["fields"]["thread"] \
                == "serving-finalize"
            with open(os.path.join(bundle, "metrics.json")) as f:
                snap = json.load(f)
            assert "zoo_serving_requests_total" in snap
            with open(os.path.join(bundle, "inflight.json")) as f:
                inflight = json.load(f)
            assert "req-crash" in inflight["request_ids"]
        finally:
            if worker is not None:
                worker.stop()
            chaos.uninstall()
            rec.uninstall()
            get_inflight().clear()

    def test_inflight_clears_on_normal_serving(self):
        """The happy path keeps the registry empty: every answered
        request is discarded at finalize."""
        from analytics_zoo_tpu.serving.queues import (
            InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.worker import ServingWorker

        class Echo:
            def predict(self, x):
                return np.asarray(x, np.float32)

        in_q, out_q = InputQueue(), OutputQueue()
        for i in range(6):
            in_q.enqueue(f"ok-{i}", x=np.ones(2, np.float32))
        worker = ServingWorker(Echo(), in_q, out_q, batch_size=4,
                               timeout_ms=2.0)
        worker.run(max_batches=3, wait_timeout=0.1)
        assert not any(u.startswith("ok-")
                       for u in get_inflight().snapshot())

    def test_unwritable_dir_degrades_gracefully(self, tmp_path):
        """install() over an uncreatable bundle root must not raise --
        the crash-observability add-on must never BE the crash."""
        blocker = tmp_path / "file"
        blocker.write_text("x")  # a FILE where the dir should go
        rec = FlightRecorder(out_dir=str(blocker / "nested"))
        try:
            rec.install()  # logs a warning, still installs hooks
            assert getattr(sys.excepthook, "__self__", None) is rec
            assert rec.write_postmortem("unit") is None  # dump fails,
        finally:                                         # never raises
            rec.uninstall()

    def test_sigterm_over_sig_ign_stays_ignored(self, tmp_path):
        """A host that deliberately SIG_IGNs SIGTERM keeps ignoring it:
        the hook writes the bundle and returns instead of dying."""
        import signal

        prev = signal.signal(signal.SIGTERM, signal.SIG_IGN)
        rec = FlightRecorder(out_dir=str(tmp_path))
        try:
            rec.install(signals=True)
            signal.raise_signal(signal.SIGTERM)
            # still alive; exactly one signal bundle exists
            bundles = [d for d in os.listdir(tmp_path)
                       if d.startswith("postmortem-")]
            assert len(bundles) == 1
            with open(os.path.join(tmp_path, bundles[0],
                                   "manifest.json")) as f:
                assert json.load(f)["reason"] == \
                    f"signal_{int(signal.SIGTERM)}"
        finally:
            rec.uninstall()
            signal.signal(signal.SIGTERM, prev)

    def test_reentrant_write_guard(self, tmp_path):
        """A crash inside the dump (or a second crash racing it) must
        not recurse into another bundle."""
        rec = FlightRecorder(out_dir=str(tmp_path))
        results = []
        orig = rec._write_bundle

        def reentrant_bundle(reason, exc, thread):
            results.append(rec.write_postmortem("nested"))  # re-enter
            return orig(reason, exc, thread)

        rec._write_bundle = reentrant_bundle
        path = rec.write_postmortem("outer")
        assert path is not None
        assert results == [None]  # nested write refused, no recursion


# ---------------------------------------------------------------- #
# /debug endpoints                                                 #
# ---------------------------------------------------------------- #
@pytest.fixture()
def debug_http_stack():
    from analytics_zoo_tpu.serving.http_frontend import HttpFrontend
    from analytics_zoo_tpu.serving.queues import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.worker import ServingWorker

    class Echo:
        def predict(self, x):
            return np.asarray(x, np.float32)

    in_q, out_q = InputQueue(maxlen=64), OutputQueue()
    worker = ServingWorker(Echo(), in_q, out_q, batch_size=4,
                           timeout_ms=2.0).start()
    fe = HttpFrontend(in_q, out_q, worker=worker,
                      request_timeout=10).start()
    yield fe
    fe.stop()
    worker.stop()


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, json.loads(resp.read())


class TestDebugEndpoints:
    def test_debug_events_tail_and_filter(self, debug_http_stack):
        fe = debug_http_stack
        ev.emit("batch_cap_change", "serving", cap=16, prev=8, depth=20)
        status, body = _get_json(fe.address + "/debug/events")
        assert status == 200
        assert body["ring_len"] >= 1
        types = [e["type"] for e in body["events"]]
        assert "batch_cap_change" in types
        # frontend_start was emitted by the fixture's start()
        assert "frontend_start" in types
        # filter by type
        status, body = _get_json(
            fe.address + "/debug/events?type=batch_cap_change&n=1")
        assert status == 200
        assert len(body["events"]) == 1
        e = body["events"][0]
        assert e["type"] == "batch_cap_change"
        assert e["fields"]["cap"] == 16
        # filter by subsystem yields only that subsystem
        status, body = _get_json(
            fe.address + "/debug/events?subsystem=serving")
        assert all(e["subsystem"] == "serving"
                   for e in body["events"])

    def test_debug_events_bad_n_defaults(self, debug_http_stack):
        status, body = _get_json(
            debug_http_stack.address + "/debug/events?n=bogus")
        assert status == 200 and "events" in body

    def test_debug_vars(self, debug_http_stack):
        status, body = _get_json(
            debug_http_stack.address + "/debug/vars")
        assert status == 200
        assert body["config"]["zoo.serving.batch_size"] == \
            get_config().get("zoo.serving.batch_size")
        assert body["config"]["zoo.obs.recompile.threshold"] == \
            get_config().get("zoo.obs.recompile.threshold")
        assert body["build"]["python"] == sys.version.split()[0]
        assert body["process"]["pid"] == os.getpid()
        assert body["process"]["uptime_s"] >= 0
        assert isinstance(body["inflight_requests"], list)

    def test_debug_routes_counted_not_404(self, debug_http_stack):
        fam = get_registry().get("zoo_http_requests_total")
        before = fam.labels(route="/debug/vars", code="200").value
        _get_json(debug_http_stack.address + "/debug/vars")
        assert fam.labels(route="/debug/vars",
                          code="200").value == before + 1


# ---------------------------------------------------------------- #
# reporter shutdown flush                                          #
# ---------------------------------------------------------------- #
class TestReporterShutdown:
    def test_stop_flushes_final_rollup(self):
        from analytics_zoo_tpu.obs.metrics import MetricsRegistry
        from analytics_zoo_tpu.obs.reporter import Reporter

        r = MetricsRegistry()
        c = r.counter("zoo_test_final_total")
        rep = Reporter(registry=r, interval=60.0).start()
        try:
            c.inc(7)  # lands mid-interval: only the flush can see it
        finally:
            rep.stop()
        final = ev.get_event_log().tail(type="reporter_final")
        assert final, "no reporter_final event"
        assert "zoo_test_final_total" in final[-1]["fields"]["rollup"]

    def test_stop_without_flush(self):
        from analytics_zoo_tpu.obs.metrics import MetricsRegistry
        from analytics_zoo_tpu.obs.reporter import Reporter

        r = MetricsRegistry()
        rep = Reporter(registry=r, interval=60.0).start()
        n = len(ev.get_event_log().tail(type="reporter_final"))
        rep.stop(flush=False)
        assert len(ev.get_event_log().tail(type="reporter_final")) == n

    def test_atexit_registration_lifecycle(self):
        import atexit

        from analytics_zoo_tpu.obs.metrics import MetricsRegistry
        from analytics_zoo_tpu.obs.reporter import Reporter

        rep = Reporter(registry=MetricsRegistry(), interval=60.0)
        assert not rep._atexit_registered
        rep.start()
        assert rep._atexit_registered
        rep.stop()
        assert not rep._atexit_registered
        # stopping again is a no-op (atexit.unregister of a
        # never-registered callable must not raise)
        rep.stop()
        atexit.unregister(rep.stop)
