"""Unified observability layer (ISSUE-2): registry, exporters,
tracing, reporter, and the HTTP endpoints that surface them.

Covers the satellite checklist: registry concurrency, a Prometheus
text-format golden, the trace-context round-trip through the AZT1
queue codec, and the end-to-end assertion that one traced request
produces decode/dispatch/finalize spans sharing one trace id.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.obs import tracing
from analytics_zoo_tpu.obs.metrics import (
    MetricsRegistry, StatCore, check_metric_name, get_registry,
    snapshot_delta)


class TestStatCore:
    def test_basic_stats_and_top(self):
        s = StatCore()
        for v in (3.0, 1.0, 2.0):
            s.observe(v)
        assert s.count == 3 and s.total == 6.0
        assert s.max == 3.0 and s.min == 1.0 and s.avg == 2.0
        assert s.top(2) == [3.0, 2.0]

    def test_top_keeps_ten_largest(self):
        s = StatCore()
        for v in range(100):
            s.observe(float(v))
        assert s.top() == [float(v) for v in range(99, 89, -1)]

    def test_percentiles_from_sample_ring(self):
        s = StatCore(keep_samples=128)
        for v in range(100):
            s.observe(float(v))
        assert 45 <= s.percentile(0.5) <= 55
        assert s.percentile(0.99) >= 95
        assert StatCore().percentile(0.5) is None  # sampling off

    def test_bucket_counts_cumulative(self):
        s = StatCore(buckets=(1.0, 5.0))
        for v in (0.5, 1.0, 3.0, 100.0):
            s.observe(v)
        assert s.bucket_counts() == [(1.0, 2), (5.0, 3),
                                     (float("inf"), 4)]


class TestRegistry:
    def test_idempotent_registration_and_mismatch(self):
        r = MetricsRegistry()
        c1 = r.counter("zoo_test_a_total", "a")
        assert r.counter("zoo_test_a_total") is c1
        with pytest.raises(ValueError):
            r.gauge("zoo_test_a_total")  # kind mismatch
        with pytest.raises(ValueError):
            r.counter("zoo_test_a_total", labelnames=("x",))

    def test_histogram_reregistration_params_must_match(self):
        r = MetricsRegistry()
        h = r.histogram("zoo_test_j_seconds", buckets=(0.1, 1.0),
                        keep_samples=16)
        assert r.histogram("zoo_test_j_seconds", buckets=(1.0, 0.1),
                           keep_samples=16) is h  # order-insensitive
        with pytest.raises(ValueError):
            r.histogram("zoo_test_j_seconds", buckets=(5.0,))
        with pytest.raises(ValueError):
            r.histogram("zoo_test_j_seconds", buckets=(0.1, 1.0))

    def test_name_convention_enforced(self):
        r = MetricsRegistry()
        for bad in ("requests", "zoo_requests", "zoo_serving_requests",
                    "zoo_serving_Requests_total", "zoo_x_y_parsecs"):
            with pytest.raises(ValueError):
                r.counter(bad)
        with pytest.raises(ValueError):
            r.gauge("zoo_serving_depth_total")  # _total reserved
        with pytest.raises(ValueError):
            r.counter("zoo_serving_depth_items")  # counter needs _total
        check_metric_name("zoo_serving_queue_depth_items")

    def test_labelled_family_rejects_unlabelled_convenience(self):
        r = MetricsRegistry()
        c = r.counter("zoo_test_o_total", labelnames=("reason",))
        with pytest.raises(ValueError, match=r"use \.labels"):
            c.inc()
        h = r.histogram("zoo_test_p_seconds", labelnames=("stage",))
        with pytest.raises(ValueError, match=r"use \.labels"):
            h.observe(1.0)

    def test_counter_monotonic(self):
        r = MetricsRegistry()
        c = r.counter("zoo_test_b_total")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_callback(self):
        r = MetricsRegistry()
        g = r.gauge("zoo_test_depth_items")
        g.set(3)
        assert g.value == 3
        g.set_function(lambda: 7)
        assert g.value == 7
        g.set_function(lambda: 1 / 0)  # raising callback -> last set()
        assert g.value == 3

    def test_concurrent_counters_and_histograms(self):
        """The registry's lock discipline: N threads hammering one
        counter + one labelled histogram lose no increments."""
        r = MetricsRegistry()
        c = r.counter("zoo_test_c_total")
        h = r.histogram("zoo_test_lat_seconds", labelnames=("stage",),
                        buckets=(0.5, 1.0))
        n_threads, per_thread = 8, 2000

        def work(i):
            child = h.labels(stage=f"s{i % 2}")
            for _ in range(per_thread):
                c.inc()
                child.observe(0.25)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert c.value == n_threads * per_thread
        total = sum(h.labels(stage=f"s{i}").snapshot()["count"]
                    for i in range(2))
        assert total == n_threads * per_thread

    def test_prometheus_text_golden(self):
        """Exact exposition-format output for a fixed registry state."""
        r = MetricsRegistry()
        c = r.counter("zoo_test_reqs_total", "requests served")
        c.inc(3)
        g = r.gauge("zoo_test_queue_depth_items", "queue depth")
        g.set(5)
        h = r.histogram("zoo_test_wait_seconds", "wait time",
                        labelnames=("stage",), buckets=(0.1, 1.0))
        h.labels(stage="decode").observe(0.05)
        h.labels(stage="decode").observe(0.5)
        assert r.prometheus_text() == (
            "# HELP zoo_test_queue_depth_items queue depth\n"
            "# TYPE zoo_test_queue_depth_items gauge\n"
            "zoo_test_queue_depth_items 5\n"
            "# HELP zoo_test_reqs_total requests served\n"
            "# TYPE zoo_test_reqs_total counter\n"
            "zoo_test_reqs_total 3\n"
            "# HELP zoo_test_wait_seconds wait time\n"
            "# TYPE zoo_test_wait_seconds histogram\n"
            'zoo_test_wait_seconds_bucket{stage="decode",le="0.1"} 1\n'
            'zoo_test_wait_seconds_bucket{stage="decode",le="1"} 2\n'
            'zoo_test_wait_seconds_bucket{stage="decode",le="+Inf"} 2\n'
            'zoo_test_wait_seconds_sum{stage="decode"} 0.55\n'
            'zoo_test_wait_seconds_count{stage="decode"} 2\n')

    def test_label_escaping(self):
        r = MetricsRegistry()
        c = r.counter("zoo_test_esc_total", labelnames=("path",))
        c.labels(path='a"b\\c\nd').inc()
        text = r.prometheus_text()
        assert r'path="a\"b\\c\nd"' in text

    def test_json_snapshot_shape(self):
        r = MetricsRegistry()
        r.counter("zoo_test_d_total").inc(2)
        h = r.histogram("zoo_test_e_seconds", buckets=(1.0,))
        h.observe(0.5)
        snap = r.snapshot()
        assert snap["zoo_test_d_total"]["values"][""] == 2
        hs = snap["zoo_test_e_seconds"]["values"][""]
        assert hs["count"] == 1 and hs["sum"] == 0.5
        assert hs["buckets"] == [[1.0, 1], ["+Inf", 1]]
        json.dumps(snap)  # must be JSON-able
        assert "buckets" not in \
            r.snapshot(with_buckets=False)["zoo_test_e_seconds"][
                "values"][""]

    def test_snapshot_delta_interval_view(self):
        r = MetricsRegistry()
        c = r.counter("zoo_test_k_total")
        h = r.histogram("zoo_test_l_seconds")
        g = r.gauge("zoo_test_m_items")
        c.inc(5)
        h.observe(10.0)  # pre-interval: a big outlier
        before = r.snapshot(with_buckets=False)
        c.inc(2)
        h.observe(0.5)
        g.set(3)
        delta = snapshot_delta(before, r.snapshot(with_buckets=False))
        assert delta["zoo_test_k_total"]["values"][""] == 2
        hs = delta["zoo_test_l_seconds"]["values"][""]
        # only the interval's observation: the 10.0 outlier from
        # before the window must not blend in
        assert hs == {"count": 1, "avg": 0.5}
        assert delta["zoo_test_m_items"]["values"][""] == 3
        # idle interval (gauge back at zero) -> empty delta:
        # untouched counters AND idle histograms are pruned
        g.set(0)
        assert snapshot_delta(r.snapshot(False), r.snapshot(False)) \
            == {}

    def test_histogram_time_context(self):
        r = MetricsRegistry()
        h = r.histogram("zoo_test_f_seconds")
        with h.time():
            pass
        assert h.snapshot()["count"] == 1


class TestTimerShims:
    """Both historical timer APIs survive on the shared StatCore."""

    def test_serving_timer_summary_shape(self):
        from analytics_zoo_tpu.serving.timer import Timer

        t = Timer(keep_samples=64)
        with t.timing("stage_a"):
            pass
        t.record("stage_a", 0.5)
        t.gauge("depth", 3)
        s = t.summary()
        a = s["stage_a"]
        assert a["count"] == 2
        for k in ("total_s", "avg_s", "max_s", "min_s", "top10_avg_s",
                  "p50_s", "p99_s"):
            assert k in a, k
        g = s["gauges"]["depth"]
        assert g["avg"] == 3 and g["count"] == 1
        t.reset()
        assert t.summary() == {}

    def test_serving_timer_mirrors_into_registry(self):
        from analytics_zoo_tpu.serving.timer import Timer

        r = MetricsRegistry()
        fam = r.histogram("zoo_test_stage_duration_seconds",
                          labelnames=("stage",))
        t = Timer(mirror=fam)
        t.record("decode", 0.25)
        t.record("decode", 0.75)
        child = fam.labels(stage="decode")
        snap = child.snapshot()
        assert snap["count"] == 2 and snap["sum"] == 1.0

    def test_common_log_timer_stat(self):
        from analytics_zoo_tpu.common.log import Timer, TimerStat

        st = TimerStat("x")
        st.record(2.0)
        st.record(1.0)
        assert st.count == 2 and st.avg == 1.5
        assert st.top(1) == [2.0]
        assert "[x]" in st.summary()
        # the k parameter bounds top-k retention (pre-dedup contract)
        wide = TimerStat("w", k=20)
        for v in range(20):
            wide.record(float(v))
        assert len(wide.top(20)) == 20
        narrow = TimerStat("n", k=3)
        for v in range(10):
            narrow.record(float(v))
        assert narrow.top(10) == [9.0, 8.0, 7.0]
        timer = Timer()
        with timer.timing("y"):
            pass
        assert timer.stat("y").count == 1


# ---------------------------------------------------------------- #
# tracing                                                          #
# ---------------------------------------------------------------- #
@pytest.fixture()
def tracing_on():
    cfg = get_config()
    cfg.set("zoo.obs.trace.enabled", True)
    tracing.get_tracer().clear()
    try:
        yield
    finally:
        cfg.unset("zoo.obs.trace.enabled")
        tracing.get_tracer().clear()


class TestTracing:
    def test_disabled_by_default(self):
        assert not tracing.enabled()
        with tracing.maybe_trace("x") as tid:
            assert tid is None
            assert tracing.current_trace_id() is None

    def test_trace_context_nesting(self):
        with tracing.trace_context("outer"):
            assert tracing.current_trace_id() == "outer"
            with tracing.trace_context("inner"):
                assert tracing.current_trace_id() == "inner"
            assert tracing.current_trace_id() == "outer"
        assert tracing.current_trace_id() is None

    def test_azt1_codec_roundtrip(self):
        """The trace id rides the AZT1 blob as __trace__ and never
        leaks into the request tensors; legacy 3-tuple decode and
        trace-less blobs are unchanged."""
        from analytics_zoo_tpu.serving.queues import (
            _decode_full, _decode_traced, _encode)

        blob = _encode("r1", {"x": np.arange(3.0)}, reply_to="s9",
                       trace_id="tid-42")
        uri, tensors, reply, trace = _decode_traced(blob)
        assert (uri, reply, trace) == ("r1", "s9", "tid-42")
        assert set(tensors) == {"x"}
        np.testing.assert_array_equal(tensors["x"], np.arange(3.0))
        # historical 3-tuple API unchanged
        assert _decode_full(blob)[0] == "r1"
        assert len(_decode_full(blob)) == 3
        # no trace -> None, no extra wire bytes
        plain = _encode("r2", {"x": np.zeros(1)})
        assert _decode_traced(plain)[3] is None
        assert len(plain) < len(_encode("r2", {"x": np.zeros(1)},
                                        trace_id="tid-42"))

    def test_enqueue_picks_up_thread_context(self, tracing_on):
        from analytics_zoo_tpu.serving.queues import (
            InputQueue, _decode_traced)

        q = InputQueue()
        with tracing.trace_context("ctx-7"):
            assert q.enqueue("a", x=np.zeros(2))
        assert q.enqueue("b", x=np.zeros(2))  # outside: no trace
        assert _decode_traced(q.queue.get(0))[3] == "ctx-7"
        assert _decode_traced(q.queue.get(0))[3] is None

    def test_chrome_trace_export(self, tmp_path):
        t = tracing.Tracer(max_spans=16)
        t.add_span("decode", "t1", 1.0, 1.5, batch=4)
        t.add_span("finalize", "t2", 2.0, 2.25)
        out = t.chrome_trace()
        events = [e for e in out["traceEvents"] if e["ph"] == "X"]
        assert len(events) == 2
        e = next(ev for ev in events if ev["name"] == "decode")
        assert e["dur"] == pytest.approx(5e5)
        assert e["args"]["trace_id"] == "t1"
        assert e["args"]["batch"] == 4
        # filtered export + file dump
        assert len([ev for ev in t.chrome_trace("t1")["traceEvents"]
                    if ev["ph"] == "X"]) == 1
        path = t.dump_chrome_trace(str(tmp_path / "trace.json"))
        with open(path) as f:
            assert json.load(f)["traceEvents"]

    def test_span_ring_bounded(self):
        t = tracing.Tracer(max_spans=4)
        for i in range(10):
            t.add_span("s", f"t{i}", 0.0, 1.0)
        spans = t.spans()
        assert len(spans) == 4
        assert spans[0]["trace_id"] == "t6"


class _EchoModel:
    def predict(self, x):
        return np.asarray(x, np.float32) * 2.0


class TestEndToEndTracing:
    def test_request_spans_all_three_stages(self, tracing_on):
        """One traced request through the pipelined engine produces
        decode + dispatch + finalize spans sharing its trace id, in
        stage order, exportable as Chrome trace JSON."""
        from analytics_zoo_tpu.serving.queues import (
            InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.worker import ServingWorker

        in_q, out_q = InputQueue(), OutputQueue()
        with tracing.maybe_trace("client_request") as tid:
            assert tid is not None
            assert in_q.enqueue("r1", x=np.ones(3, np.float32))
        worker = ServingWorker(_EchoModel(), in_q, out_q, batch_size=4,
                               timeout_ms=2.0)
        worker.run(max_batches=1, wait_timeout=0.2)
        uri, tensors = out_q.dequeue(timeout=2)
        assert uri == "r1"

        spans = tracing.get_tracer().spans(tid)
        names = [s["name"] for s in spans]
        for stage in ("decode", "dispatch", "finalize"):
            assert stage in names, f"missing {stage} span: {names}"
        assert "client_request" in names
        # stage order holds within the trace
        t0 = {s["name"]: s["t0"] for s in spans}
        assert t0["decode"] <= t0["dispatch"] <= t0["finalize"]
        events = tracing.get_tracer().chrome_trace(tid)["traceEvents"]
        assert {e["name"] for e in events if e["ph"] == "X"} >= {
            "decode", "dispatch", "finalize"}

    def test_untraced_requests_emit_no_spans(self):
        from analytics_zoo_tpu.serving.queues import (
            InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.worker import ServingWorker

        tracing.get_tracer().clear()
        in_q, out_q = InputQueue(), OutputQueue()
        in_q.enqueue("r1", x=np.ones(3, np.float32))
        worker = ServingWorker(_EchoModel(), in_q, out_q, batch_size=4,
                               timeout_ms=2.0)
        worker.run(max_batches=1, wait_timeout=0.2)
        assert out_q.dequeue(timeout=2) is not None
        assert tracing.get_tracer().spans() == []

    def test_sync_engine_also_traces(self, tracing_on):
        """A depth-1 window (one batch in flight, no dispatch overlap)
        still records every stage span of a traced request."""
        from analytics_zoo_tpu.serving.queues import (
            InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.worker import ServingWorker

        in_q, out_q = InputQueue(), OutputQueue()
        with tracing.maybe_trace("client_request") as tid:
            in_q.enqueue("r1", x=np.ones(3, np.float32))
        worker = ServingWorker(_EchoModel(), in_q, out_q, batch_size=4,
                               timeout_ms=2.0, pipeline_depth=1)
        worker.run(max_batches=2, wait_timeout=0.2)
        assert out_q.dequeue(timeout=2) is not None
        names = {s["name"] for s in tracing.get_tracer().spans(tid)}
        assert names >= {"decode", "dispatch", "finalize"}


# ---------------------------------------------------------------- #
# HTTP endpoints                                                   #
# ---------------------------------------------------------------- #
@pytest.fixture()
def obs_http_stack():
    from analytics_zoo_tpu.serving.http_frontend import HttpFrontend
    from analytics_zoo_tpu.serving.queues import InputQueue, OutputQueue
    from analytics_zoo_tpu.serving.worker import ServingWorker

    in_q, out_q = InputQueue(maxlen=64), OutputQueue()
    worker = ServingWorker(_EchoModel(), in_q, out_q, batch_size=4,
                           timeout_ms=2.0).start()
    fe = HttpFrontend(in_q, out_q, worker=worker,
                      request_timeout=10).start()
    yield fe, worker
    fe.stop()
    worker.stop()


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers, resp.read()


class TestHttpObservability:
    def test_prometheus_exposition(self, obs_http_stack):
        fe, _ = obs_http_stack
        status, headers, body = _get(fe.address + "/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        text = body.decode()
        assert "# TYPE zoo_serving_requests_total counter" in text
        assert "# TYPE zoo_serving_stage_duration_seconds histogram" \
            in text
        assert "# TYPE zoo_serving_queue_depth_items gauge" in text
        # every sample line parses: name{labels} value
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            name, value = line.rsplit(" ", 1)
            assert name.startswith("zoo_"), line
            float(value)

    def test_metrics_json_snapshot(self, obs_http_stack):
        fe, _ = obs_http_stack
        status, _, body = _get(fe.address + "/metrics.json")
        assert status == 200
        snap = json.loads(body)
        assert "registry" in snap and "frontend" in snap
        assert snap["registry"]["zoo_serving_requests_total"][
            "type"] == "counter"

    def test_healthz_alive_and_dead(self, obs_http_stack):
        fe, worker = obs_http_stack
        status, _, body = _get(fe.address + "/healthz")
        health = json.loads(body)
        assert status == 200 and health["status"] == "ok"
        assert "uptime_s" in health
        # a dead worker thread flips liveness to 503
        worker.stop()
        worker._thread = threading.Thread(target=lambda: None)
        worker._thread.start()
        worker._thread.join()
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            _get(fe.address + "/healthz")
        assert exc_info.value.code == 503
        assert json.loads(exc_info.value.read())["status"] == \
            "worker_dead"
        worker._thread = None

    def test_query_string_does_not_404_known_routes(self,
                                                    obs_http_stack):
        fe, _ = obs_http_stack
        status, headers, _ = _get(fe.address + "/metrics?collect=x")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        status, _, body = _get(fe.address + "/healthz?probe=1")
        assert status == 200 and json.loads(body)["status"] == "ok"

    def test_pipeline_gauges_reset_after_run(self):
        from analytics_zoo_tpu.serving.queues import (
            InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.worker import ServingWorker

        in_q, out_q = InputQueue(), OutputQueue()
        for i in range(12):
            in_q.enqueue(f"r{i}", x=np.ones(3, np.float32))
        worker = ServingWorker(_EchoModel(), in_q, out_q, batch_size=4,
                               timeout_ms=2.0)
        worker.run(max_batches=6, wait_timeout=0.1)
        reg = get_registry()
        assert reg.get("zoo_serving_inflight_batches_items").value == 0
        assert reg.get("zoo_serving_queue_depth_items").value == 0

    def test_unknown_path_404_json(self, obs_http_stack):
        fe, _ = obs_http_stack
        for method, path in (("GET", "/nope"), ("GET", "/metrics2"),
                             ("POST", "/predictx")):
            req = urllib.request.Request(
                fe.address + path, method=method,
                data=b"{}" if method == "POST" else None)
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                urllib.request.urlopen(req, timeout=10)
            assert exc_info.value.code == 404
            body = json.loads(exc_info.value.read())
            assert body["error"] == "not found" and body["path"] == path

    def test_traced_predict_end_to_end(self, obs_http_stack,
                                       tracing_on):
        """HTTP /predict under tracing: the response echoes a trace id
        whose spans cover frontend + all worker stages, and /trace
        serves the Chrome export."""
        fe, _ = obs_http_stack
        req = urllib.request.Request(
            fe.address + "/predict",
            data=json.dumps({"inputs": {"x": [1.0, 2.0]}}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=15) as resp:
            body = json.loads(resp.read())
        assert body["predictions"]["output"] == [2.0, 4.0]
        tid = body["trace_id"]
        names = {s["name"]
                 for s in tracing.get_tracer().spans(tid)}
        assert names >= {"http_request", "decode", "dispatch",
                         "finalize"}
        status, _, trace_body = _get(fe.address + "/trace")
        assert status == 200
        events = json.loads(trace_body)["traceEvents"]
        assert any(e.get("args", {}).get("trace_id") == tid
                   for e in events)

    def test_untraced_predict_has_no_trace_id(self, obs_http_stack):
        fe, _ = obs_http_stack
        req = urllib.request.Request(
            fe.address + "/predict",
            data=json.dumps({"inputs": {"x": [1.0, 2.0]}}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=15) as resp:
            body = json.loads(resp.read())
        assert "trace_id" not in body

    def test_http_request_counter_by_route(self, obs_http_stack):
        fe, _ = obs_http_stack
        fam = get_registry().get("zoo_http_requests_total")
        before = fam.labels(route="/healthz", code="200").value
        _get(fe.address + "/healthz")
        assert fam.labels(route="/healthz",
                          code="200").value == before + 1


# ---------------------------------------------------------------- #
# reporter                                                         #
# ---------------------------------------------------------------- #
class TestReporter:
    def test_rollup_rates_and_latency(self):
        from analytics_zoo_tpu.obs.reporter import Reporter

        r = MetricsRegistry()
        c = r.counter("zoo_test_g_total")
        h = r.histogram("zoo_test_h_seconds")
        g = r.gauge("zoo_test_i_items")
        rep = Reporter(registry=r, interval=60.0)
        assert rep.tick(dt=1.0) == "idle"
        c.inc(50)
        h.observe(0.010)
        h.observe(0.030)
        g.set(4)
        line = rep.tick(dt=2.0)
        assert "zoo_test_g_total: 25.0/s" in line
        assert "zoo_test_h_seconds: n=2 mean=20.00ms" in line
        assert "zoo_test_i_items: 4" in line
        # rolled baseline: an idle interval reports idle again
        g.set(0)
        assert rep.tick(dt=1.0) == "idle"

    def test_rates_use_measured_elapsed_time(self):
        import time as _time

        from analytics_zoo_tpu.obs.reporter import Reporter

        r = MetricsRegistry()
        c = r.counter("zoo_test_n_total")
        rep = Reporter(registry=r, interval=0.01)  # configured 10ms
        _time.sleep(0.2)  # a "delayed" cycle
        c.inc(10)
        line = rep.tick()  # no explicit dt: measured elapsed governs
        rate = float(line.split(": ")[1].rstrip("/s"))
        # 10 / ~0.2s ≈ 50/s; dividing by the configured 0.01 would
        # claim 1000/s
        assert rate < 200, line

    def test_thread_lifecycle_and_config_gate(self):
        from analytics_zoo_tpu.obs.reporter import (
            Reporter, maybe_start_reporter)

        assert maybe_start_reporter() is None  # default interval 0
        cfg = get_config()
        cfg.set("zoo.obs.report.interval", 0.05)
        try:
            rep = maybe_start_reporter()
            assert rep is not None and rep._thread.is_alive()
            rep.stop()
            assert rep._thread is None
        finally:
            cfg.unset("zoo.obs.report.interval")
        with pytest.raises(ValueError):
            Reporter(registry=MetricsRegistry(), interval=0).start()
