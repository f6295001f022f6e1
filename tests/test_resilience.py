"""Fault-tolerant serving (ISSUE-5): supervised workers, request
deadlines, circuit breaker, load shedding, and the deterministic chaos
harness.

The acceptance contract under test: with faults injected at the exact
seams the Supervisor watches (worker-thread crash mid-batch, wedged
dispatch, flaky backend, queue saturation) the engine recovers without
operator action and every admitted request gets exactly one reply --
result or structured error; with every resilience/chaos knob at its
default (off), behavior is byte-identical to the plain PR-1 pipeline.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.obs.events import get_event_log
from analytics_zoo_tpu.serving import chaos
from analytics_zoo_tpu.serving.chaos import (
    ChaosCrash, ChaosError, ChaosInjector, parse_spec)
from analytics_zoo_tpu.serving.queues import (
    InputQueue, OutputQueue, _decode_request, _encode)
from analytics_zoo_tpu.serving.resilience import (
    CircuitBreaker, RequestLedger, Supervisor)
from analytics_zoo_tpu.serving.worker import (
    DEADLINE_PREFIX, ERROR_KEY, ServingWorker)


# ------------------------------------------------------------ helpers --
class _LazyResult:
    def __init__(self, value):
        self._value = np.asarray(value)

    def __array__(self, dtype=None, copy=None):
        a = self._value
        return a.astype(dtype) if dtype is not None else a


class _AsyncEcho:
    """predict_async doubles the input (the pipeline tests' model)."""

    def __init__(self):
        self.dispatched = 0

    def predict_async(self, x):
        self.dispatched += 1
        return _LazyResult(np.asarray(x, np.float64) * 2.0), len(x)


class _FlakyModel:
    """predict fails while ``failing`` is set; counts calls."""

    def __init__(self):
        self.failing = True
        self.calls = 0

    def predict(self, x):
        self.calls += 1
        if self.failing:
            raise RuntimeError("backend down")
        return np.asarray(x, np.float64) * 2.0


def _fill(n, in_q=None, shape=(2,)):
    if in_q is None:  # NOT `in_q or ...`: an empty InputQueue is falsy
        in_q = InputQueue()
    out_q = OutputQueue()
    for i in range(n):
        assert in_q.enqueue(f"r{i:04d}",
                            x=np.full(shape, float(i), np.float32))
    return in_q, out_q


def _drain_until(out_q, n, timeout=15.0):
    """Collect replies until n DISTINCT uris answered (duplicates are
    recorded too, for the exactly-once assertions)."""
    deadline = time.time() + timeout
    replies = []
    seen = set()
    while len(seen) < n and time.time() < deadline:
        item = out_q.dequeue(timeout=0.1)
        if item is not None:
            replies.append(item)
            seen.add(item[0])
    return replies


def _events_since(seq, type=None):
    return [e for e in get_event_log().tail(type=type)
            if e["seq"] > seq]


@pytest.fixture(autouse=True)
def _no_leftover_chaos():
    yield
    chaos.uninstall()


# ------------------------------------------------------- chaos harness --
class TestChaosHarness:
    def test_parse_spec_grammar(self):
        rules = parse_spec("crash:dispatch:at=3;"
                           "sleep:decode:every=5:dur=0.2;"
                           "error:finalize:p=0.05;drop:push:p=0.5")
        assert [(r.kind, r.seam) for r in rules] == [
            ("crash", "dispatch"), ("sleep", "decode"),
            ("error", "finalize"), ("drop", "push")]
        assert rules[0].at == 3 and rules[1].every == 5
        assert rules[1].dur == pytest.approx(0.2)
        assert rules[2].p == pytest.approx(0.05)
        for bad in ("crash", "boom:dispatch", "crash:nowhere",
                    "crash:dispatch:when=3", "drop:decode"):
            with pytest.raises(ValueError):
                parse_spec(bad)

    def test_at_trigger_fires_exactly_once(self):
        inj = ChaosInjector(parse_spec("error:dispatch:at=2"))
        inj.fire("dispatch")
        with pytest.raises(ChaosError):
            inj.fire("dispatch")
        for _ in range(10):  # never again, even across "restarts"
            inj.fire("dispatch")
        assert inj.counts() == {"dispatch:error": 1}

    def test_seeded_schedule_is_deterministic(self):
        def schedule(seed):
            inj = ChaosInjector(parse_spec("error:decode:p=0.3"),
                                seed=seed)
            fired = []
            for _ in range(64):
                try:
                    inj.fire("decode")
                    fired.append(False)
                except ChaosError:
                    fired.append(True)
            return fired

        assert schedule(7) == schedule(7)
        assert any(schedule(7)) and not all(schedule(7))
        assert schedule(7) != schedule(8)  # seed actually matters

    def test_chaos_point_disabled_is_noop(self):
        assert chaos.get_injector() is None
        assert chaos.chaos_point("dispatch") is False

    def test_install_from_config(self):
        cfg = get_config()
        cfg.set("zoo.serving.chaos.enabled", True)
        cfg.set("zoo.serving.chaos.spec", "sleep:pull:at=999")
        cfg.set("zoo.serving.chaos.seed", 3)
        try:
            inj = chaos.maybe_install_from_config()
            assert inj is not None and chaos.get_injector() is inj
            assert inj.rules[0].seam == "pull"
        finally:
            chaos.uninstall()
            cfg.unset("zoo.serving.chaos.enabled")
            cfg.unset("zoo.serving.chaos.spec")
            cfg.unset("zoo.serving.chaos.seed")
        assert chaos.maybe_install_from_config() is None

    def test_drop_reply_loses_results_but_not_the_worker(self):
        chaos.install(ChaosInjector(parse_spec("drop:push:p=1.0")))
        in_q, out_q = _fill(6)
        worker = ServingWorker(_AsyncEcho(), in_q, out_q, batch_size=2,
                               timeout_ms=1.0)
        served = worker.run(max_batches=6, wait_timeout=0.02)
        assert served == 6              # the engine accounted for all
        assert out_q.dequeue_all() == []  # ...but every reply was shed


# ---------------------------------------------------- circuit breaker --
class TestCircuitBreaker:
    def test_open_halfopen_close_cycle(self):
        clock = [0.0]
        br = CircuitBreaker(threshold=3, cooldown_s=5.0,
                            clock=lambda: clock[0])
        seq0 = get_event_log().tail()[-1]["seq"] if get_event_log() \
            .tail() else 0
        assert br.state == "closed" and br.allow()
        br.record_failure()
        br.record_failure()
        br.record_success()   # success resets the consecutive count
        br.record_failure()
        br.record_failure()
        assert br.state == "closed"
        br.record_failure()   # 3rd consecutive -> open
        assert br.state == "open"
        assert not br.allow() and not br.allow()
        clock[0] = 5.1        # cooldown elapsed: ONE half-open probe
        assert br.allow()
        assert br.state == "half_open"
        assert not br.allow()  # probe still in flight
        br.record_success()
        assert br.state == "closed" and br.allow()
        types = [e["type"] for e in _events_since(seq0)]
        assert "circuit_open" in types
        assert "circuit_half_open" in types
        assert "circuit_closed" in types

    def test_vanished_probe_rearms_after_cooldown(self):
        """A probe that never reports back (its thread crashed, or it
        failed outside the predict path) must not wedge the breaker
        half-open forever: the probe slot re-arms after a cooldown."""
        clock = [0.0]
        br = CircuitBreaker(threshold=1, cooldown_s=1.0,
                            clock=lambda: clock[0])
        br.record_failure()
        clock[0] = 1.5
        assert br.allow()          # the probe... which then vanishes
        assert not br.allow()
        clock[0] = 3.0             # another cooldown later
        assert br.allow(), "vanished probe wedged the breaker"
        br.record_success()
        assert br.state == "closed"

    def test_failed_probe_reopens(self):
        clock = [0.0]
        br = CircuitBreaker(threshold=1, cooldown_s=2.0,
                            clock=lambda: clock[0])
        br.record_failure()
        assert br.state == "open"
        clock[0] = 2.5
        assert br.allow()
        br.record_failure()
        assert br.state == "open"
        assert not br.allow()  # cooldown restarted at the re-open
        clock[0] = 5.0
        assert br.allow()

    def test_breaker_in_worker_fast_fails_and_recovers(self):
        model = _FlakyModel()
        br = CircuitBreaker(threshold=2, cooldown_s=0.15)
        in_q, out_q = _fill(4)
        # max_batch_size=2: one pull is one batch of two, never grown
        worker = ServingWorker(model, in_q, out_q, batch_size=2,
                               timeout_ms=1.0, max_batch_size=2,
                               breaker=br)
        worker.run(max_batches=1, wait_timeout=0.02)  # fail #1
        worker.run(max_batches=1, wait_timeout=0.02)  # fail #2 -> open
        assert br.state == "open"
        calls = model.calls
        _fill(2, in_q=in_q)
        worker.run(max_batches=1, wait_timeout=0.02)
        assert model.calls == calls, "open breaker still dispatched"
        results = dict(out_q.dequeue_all())
        fast_failed = [v for v in results.values()
                       if "circuit_open" in str(v.get(ERROR_KEY, ""))]
        assert len(fast_failed) == 2
        model.failing = False
        time.sleep(0.2)  # past cooldown: next dispatch is the probe
        _fill(2, in_q=in_q)
        worker.run(max_batches=1, wait_timeout=0.02)
        assert br.state == "closed"
        assert worker.metrics()["breaker"]["state"] == "closed"


# ------------------------------------------------------ request ledger --
class TestRequestLedger:
    def test_record_settle_requeue_exactly_once(self):
        led = RequestLedger()
        led.record("a", b"blob-a")
        led.record("b", b"blob-b")
        led.settle(["a"])
        fresh, dead = led.take_for_requeue()
        assert fresh == [("b", b"blob-b")] and dead == []
        # second crash: b was already requeued once -> dead
        fresh, dead = led.take_for_requeue()
        assert fresh == [] and dead == [("b", b"blob-b")]
        assert len(led) == 0
        # a settled-then-reused id starts a fresh life
        led.record("b", b"blob-b2")
        fresh, _ = led.take_for_requeue()
        assert fresh == [("b", b"blob-b2")]

    def test_bounded(self):
        led = RequestLedger(max_entries=3)
        for i in range(5):
            led.record(f"u{i}", b"x")
        assert len(led) == 3 and led.dropped == 2
        assert led.outstanding() == ["u2", "u3", "u4"]


# --------------------------------------------------------- supervision --
class TestSupervisor:
    def _supervised(self, model, in_q, out_q, **worker_kw):
        worker = ServingWorker(model, in_q, out_q, batch_size=4,
                               timeout_ms=1.0, max_batch_size=4,
                               **worker_kw)
        sup = Supervisor(worker, poll_interval_s=0.03,
                         heartbeat_timeout_s=30.0,
                         backoff_base_s=0.01, backoff_max_s=0.05,
                         seed=0)
        return worker, sup

    def test_crash_mid_batch_recovers_exactly_once(self):
        """The acceptance scenario: chaos kills the dispatch thread on
        its first batch; the supervisor restarts the engine and
        re-queues the in-flight requests; every request is answered
        exactly once with the correct result."""
        chaos.install(ChaosInjector(parse_spec("crash:dispatch:at=1")))
        seq0 = get_event_log().tail()[-1]["seq"]
        in_q, out_q = _fill(8)
        worker, sup = self._supervised(_AsyncEcho(), in_q, out_q)
        worker.start()
        sup.start()
        try:
            replies = _drain_until(out_q, 8)
        finally:
            sup.stop()
            worker.stop()
        uris = [u for u, _ in replies]
        assert sorted(set(uris)) == [f"r{i:04d}" for i in range(8)]
        assert len(uris) == len(set(uris)), "duplicated replies"
        for u, tensors in replies:
            i = int(u[1:])
            np.testing.assert_allclose(tensors["output"],
                                       [2.0 * i, 2.0 * i])
        assert sup.restarts == 1
        assert [e["type"] for e in
                _events_since(seq0, type="worker_restart")] \
            == ["worker_restart"]
        assert _events_since(seq0, type="worker_crash")

    def test_double_crash_answers_with_structured_error(self):
        """A request whose re-run also dies gets ONE error reply, not
        a third run and not silence."""
        chaos.install(ChaosInjector(
            parse_spec("crash:dispatch:at=1;crash:dispatch:at=2")))
        in_q, out_q = _fill(4)
        worker, sup = self._supervised(_AsyncEcho(), in_q, out_q)
        worker.start()
        sup.start()
        try:
            replies = _drain_until(out_q, 4)
        finally:
            sup.stop()
            worker.stop()
        uris = [u for u, _ in replies]
        assert sorted(set(uris)) == [f"r{i:04d}" for i in range(4)]
        assert len(uris) == len(set(uris)), "duplicated replies"
        for _, tensors in replies:
            assert "worker died twice" in str(tensors[ERROR_KEY])
        assert sup.restarts == 2

    def test_wedged_dispatch_detected_and_restarted(self):
        """A dispatch thread stuck in a long syscall: the heartbeat
        goes stale, the supervisor abandons the thread and restarts.
        Wedge recovery is at-least-once (the zombie may still push),
        so assert coverage + recovery, not uniqueness."""
        chaos.install(ChaosInjector(
            parse_spec("sleep:dispatch:at=1:dur=1.0")))
        seq0 = get_event_log().tail()[-1]["seq"]
        in_q, out_q = _fill(8)
        worker = ServingWorker(_AsyncEcho(), in_q, out_q, batch_size=4,
                               timeout_ms=1.0, max_batch_size=4)
        sup = Supervisor(worker, poll_interval_s=0.03,
                         heartbeat_timeout_s=0.25,
                         backoff_base_s=0.01, backoff_max_s=0.05,
                         seed=0)
        worker.start()
        sup.start()
        try:
            replies = _drain_until(out_q, 8)
        finally:
            sup.stop()
            worker.stop()
            time.sleep(1.1)  # let the zombie thread wake + exit
        assert sorted({u for u, _ in replies}) == \
            [f"r{i:04d}" for i in range(8)]
        restarts = _events_since(seq0, type="worker_restart")
        assert restarts and restarts[0]["fields"]["reason"] == "wedged"

    def test_operator_stop_is_not_restarted(self):
        in_q, out_q = _fill(2)
        worker, sup = self._supervised(_AsyncEcho(), in_q, out_q)
        worker.start()
        sup.start()
        try:
            _drain_until(out_q, 2)
            worker.stop()
            time.sleep(0.2)  # several poll intervals
            assert sup.restarts == 0
            assert worker._thread is None
        finally:
            sup.stop()

    def test_max_restarts_gives_up(self):
        chaos.install(ChaosInjector(parse_spec("crash:pull:every=1")))
        seq0 = get_event_log().tail()[-1]["seq"]
        in_q, out_q = _fill(2)
        worker = ServingWorker(_AsyncEcho(), in_q, out_q, batch_size=2,
                               timeout_ms=1.0)
        sup = Supervisor(worker, poll_interval_s=0.02,
                         heartbeat_timeout_s=30.0,
                         backoff_base_s=0.005, backoff_max_s=0.01,
                         max_restarts=2, seed=0)
        worker.start()
        sup.start()
        try:
            deadline = time.time() + 10
            while time.time() < deadline:
                if _events_since(seq0, type="supervisor_giveup"):
                    break
                time.sleep(0.02)
        finally:
            sup.stop()
            worker.stop()
        assert sup.restarts == 2
        assert _events_since(seq0, type="supervisor_giveup")

    def test_giveup_answers_outstanding_requests_with_errors(self):
        """Giving up on the WORKER must not strand its CLIENTS: the
        final run's decoded-but-unanswered requests still get one
        structured error reply."""
        chaos.install(ChaosInjector(
            parse_spec("crash:dispatch:every=1")))
        in_q, out_q = _fill(4)
        worker = ServingWorker(_AsyncEcho(), in_q, out_q, batch_size=4,
                               timeout_ms=1.0, max_batch_size=4)
        sup = Supervisor(worker, poll_interval_s=0.02,
                         heartbeat_timeout_s=30.0,
                         backoff_base_s=0.005, backoff_max_s=0.01,
                         max_restarts=1, seed=0)
        worker.start()
        sup.start()
        try:
            replies = _drain_until(out_q, 4, timeout=10.0)
        finally:
            sup.stop()
            worker.stop()
        uris = [u for u, _ in replies]
        assert sorted(set(uris)) == [f"r{i:04d}" for i in range(4)]
        assert len(uris) == len(set(uris))
        for _, tensors in replies:
            assert "gave up" in str(tensors[ERROR_KEY])

    def test_wedged_decode_stage_detected(self):
        """A pull stuck in a hung broker recv starves the engine while
        the driver idles healthily -- the decode stage's own heartbeat
        must trip the wedge detector."""
        chaos.install(ChaosInjector(
            parse_spec("sleep:pull:at=1:dur=5.0")))
        seq0 = get_event_log().tail()[-1]["seq"]
        in_q, out_q = _fill(4)
        worker = ServingWorker(_AsyncEcho(), in_q, out_q, batch_size=4,
                               timeout_ms=1.0, max_batch_size=4)
        sup = Supervisor(worker, poll_interval_s=0.03,
                         heartbeat_timeout_s=0.25,
                         backoff_base_s=0.01, backoff_max_s=0.05,
                         seed=0)
        worker.start()
        sup.start()
        try:
            replies = _drain_until(out_q, 4, timeout=10.0)
        finally:
            sup.stop()
            worker.stop()
        assert sorted({u for u, _ in replies}) == \
            [f"r{i:04d}" for i in range(4)]
        restarts = _events_since(seq0, type="worker_restart")
        assert restarts and restarts[0]["fields"]["reason"] == "wedged"


# ----------------------------------------------------------- deadlines --
class TestDeadlines:
    def test_no_deadline_config_means_identical_wire_bytes(self):
        """Zero-overhead opt-out at the wire level: with the knob at
        its default the enqueued blob is byte-identical to a direct
        _encode (no __deadline__, no behavior change)."""
        in_q = InputQueue()
        assert in_q.deadline_ms == 0.0 and in_q.shed_depth == 0
        in_q.enqueue("u1", x=np.arange(3.0, dtype=np.float32))
        blob = in_q.queue.get(timeout=0)
        assert blob == _encode("u1",
                               {"x": np.arange(3.0, dtype=np.float32)})
        assert _decode_request(blob)[4] is None

    def test_expired_requests_rejected_with_structured_error(self):
        seq0 = get_event_log().tail()[-1]["seq"]
        in_q = InputQueue(deadline_ms=30.0)
        _fill(4, in_q=in_q)
        out_q = OutputQueue()
        blob = in_q.queue.get(timeout=0)  # sample one for the codec
        deadline = _decode_request(blob)[4]
        assert deadline is not None
        assert abs(deadline - time.time()) < 5.0
        in_q.queue.put(blob)
        time.sleep(0.08)  # everything is now past its 30ms budget
        worker = ServingWorker(_AsyncEcho(), in_q, out_q, batch_size=4,
                               timeout_ms=1.0)
        worker.run(max_batches=4, wait_timeout=0.02)
        results = dict(out_q.dequeue_all())
        assert len(results) == 4
        for tensors in results.values():
            assert str(tensors[ERROR_KEY]).startswith(DEADLINE_PREFIX)
        assert _events_since(seq0, type="deadline_exceeded")

    def test_live_requests_within_deadline_are_served(self):
        in_q = InputQueue(deadline_ms=10000.0)
        _fill(4, in_q=in_q)
        out_q = OutputQueue()
        worker = ServingWorker(_AsyncEcho(), in_q, out_q, batch_size=4,
                               timeout_ms=1.0)
        worker.run(max_batches=4, wait_timeout=0.02)
        results = dict(out_q.dequeue_all())
        assert len(results) == 4
        for uri, tensors in results.items():
            assert ERROR_KEY not in tensors
            i = float(int(uri[1:]))
            np.testing.assert_allclose(tensors["output"], [2 * i, 2 * i])

    def test_frontend_maps_deadline_error_to_504(self):
        from analytics_zoo_tpu.serving.http_frontend import HttpFrontend

        in_q, out_q = InputQueue(), OutputQueue()
        fe = HttpFrontend(in_q, out_q)
        fe.router.register("u-dl")
        out_q.queue.put(_encode(
            "u-dl", {ERROR_KEY: np.asarray(
                DEADLINE_PREFIX + ": request missed its deadline "
                                  "before dispatch")}))
        fe.router.start()
        try:
            code, payload = fe._await("u-dl",
                                      time.monotonic() + 5.0)
        finally:
            fe.router.stop()
            fe._server.server_close()
        assert code == 504
        assert payload["error"] == "deadline_exceeded"

    def test_frontend_maps_circuit_open_error_to_503(self):
        """Breaker fast-fails are a retryable capacity condition, not
        a server fault: the frontend maps the structured circuit_open
        prefix to 503 via protocol.ERROR_PREFIXES (the do_POST handler
        adds Retry-After to every 503)."""
        from analytics_zoo_tpu.serving.http_frontend import HttpFrontend
        from analytics_zoo_tpu.serving.worker import CIRCUIT_PREFIX

        in_q, out_q = InputQueue(), OutputQueue()
        fe = HttpFrontend(in_q, out_q)
        fe.router.register("u-cb")
        out_q.queue.put(_encode(
            "u-cb", {ERROR_KEY: np.asarray(
                CIRCUIT_PREFIX + ": backend dispatch suspended "
                                 "after repeated failures")}))
        fe.router.start()
        try:
            code, payload = fe._await("u-cb",
                                      time.monotonic() + 5.0)
        finally:
            fe.router.stop()
            fe._server.server_close()
        assert code == 503
        assert payload["error"] == "circuit_open"

    def test_error_status_contract(self):
        """protocol.error_status: exact or '<prefix>:'-led matches
        only -- a prefix-extending message must NOT inherit the
        mapping, and unprefixed errors stay generic (None -> 500)."""
        from analytics_zoo_tpu.serving.protocol import (
            CIRCUIT_PREFIX, DEADLINE_PREFIX, ERROR_PREFIXES,
            error_status)

        assert error_status(DEADLINE_PREFIX) == 504
        assert error_status(DEADLINE_PREFIX + ": detail") == 504
        assert error_status(CIRCUIT_PREFIX + ": detail") == 503
        assert error_status(DEADLINE_PREFIX + "_extra: x") is None
        assert error_status("boom") is None
        # every declared prefix carries a real HTTP status
        assert all(isinstance(s, int) and 400 <= s < 600
                   for s in ERROR_PREFIXES.values())


# ------------------------------------------------------- load shedding --
class TestLoadShedding:
    def test_enqueue_sheds_above_depth(self):
        seq0 = get_event_log().tail()[-1]["seq"]
        in_q = InputQueue(shed_depth=3)
        for i in range(3):
            assert in_q.enqueue(f"s{i}", x=np.zeros(2, np.float32))
        assert not in_q.enqueue("s3", x=np.zeros(2, np.float32))
        assert not in_q.enqueue("s4", x=np.zeros(2, np.float32))
        assert len(in_q) == 3
        shed_events = _events_since(seq0, type="request_shed")
        assert len(shed_events) == 1, "one event per shed episode"
        # draining re-opens admission (and a fresh episode can begin)
        in_q.queue.get(timeout=0)
        assert in_q.enqueue("s5", x=np.zeros(2, np.float32))

    def test_http_503_with_retry_after_header(self):
        from analytics_zoo_tpu.serving.http_frontend import HttpFrontend

        in_q = InputQueue(shed_depth=1)
        in_q.enqueue("pre", x=np.zeros(2, np.float32))  # at threshold
        out_q = OutputQueue()
        fe = HttpFrontend(in_q, out_q).start()
        try:
            body = json.dumps({"inputs": {"x": [1.0, 2.0]}}).encode()
            req = urllib.request.Request(
                fe.address + "/predict", data=body,
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(req, timeout=10)
            assert exc.value.code == 503
            assert exc.value.headers["Retry-After"] == "1"
            payload = json.loads(exc.value.read())
            assert "overloaded" in payload["error"]
            assert payload["retry_after_s"] == pytest.approx(1.0)
        finally:
            fe.stop()


# ------------------------------------------- zero-overhead equivalence --
class TestDisabledEquivalence:
    def test_defaults_leave_worker_unarmed(self):
        worker = ServingWorker(_AsyncEcho(), InputQueue(),
                               OutputQueue())
        assert worker.breaker is None and worker.ledger is None

    def test_pipelined_and_sync_identical_with_resilience_off(self):
        """All knobs default: every request of the stream is answered
        once with exactly the model's own answer for it."""
        rng = np.random.RandomState(11)
        stream = [(f"q{i:03d}", rng.randn(2).astype(np.float32))
                  for i in range(12)]
        in_q, out_q = InputQueue(), OutputQueue()
        for uri, x in stream:
            assert in_q.enqueue(uri, x=x)
        worker = ServingWorker(_AsyncEcho(), in_q, out_q,
                               batch_size=4, timeout_ms=2.0)
        assert worker.run(max_batches=20, wait_timeout=0.02) \
            == len(stream)
        out = dict(out_q.dequeue_all())
        assert sorted(out) == [uri for uri, _ in stream]
        for uri, x in stream:
            np.testing.assert_array_equal(out[uri]["output"],
                                          np.asarray(x, np.float64) * 2.0)


# ------------------------------------------------- manager (satellite) --
class TestManagerIdentity:
    def test_pid_reuse_no_longer_reads_as_running(self, tmp_path):
        """A state file whose pid is alive but belongs to a DIFFERENT
        process (recorded start time mismatch) must read as dead --
        and never be signalled."""
        from analytics_zoo_tpu.serving import manager

        ident = manager._proc_identity(os.getpid())
        if ident is None:
            pytest.skip("no /proc on this platform")
        sdir = tmp_path / "state"
        sdir.mkdir()
        state = {"name": "reused", "pid": os.getpid(),
                 "starttime": ident[0] + 12345, "cmdline": "other"}
        with open(sdir / "reused.json", "w") as f:
            json.dump(state, f)
        assert manager._alive(os.getpid())  # bare pid probe says yes
        assert not manager._alive_state(state)  # identity says no
        sts = manager.status(state_dir=str(sdir))
        assert len(sts) == 1 and sts[0]["running"] is False
        assert not (sdir / "reused.json").exists()  # GC'd
        # matching identity still reads as running
        good = {"name": "me", "pid": os.getpid(),
                "starttime": ident[0], "cmdline": ident[1]}
        assert manager._alive_state(good)

    def test_status_gc_reaps_dead_pid_state(self, tmp_path):
        from analytics_zoo_tpu.serving import manager

        sdir = tmp_path / "state"
        sdir.mkdir()
        with open(sdir / "dead.json", "w") as f:
            json.dump({"name": "dead", "pid": 2 ** 22 + 7}, f)
        sts = manager.status(state_dir=str(sdir))
        assert len(sts) == 1 and sts[0]["running"] is False
        assert manager.status(state_dir=str(sdir)) == []  # reaped

    def test_restart_revives_a_dead_deployment(self, tmp_path):
        """restart = stop-if-running + start from the recorded config;
        it must work when the old process is long gone (the post-OOM
        recovery move)."""
        import yaml

        from analytics_zoo_tpu.serving import manager

        cfg_path = tmp_path / "serving.yaml"
        with open(cfg_path, "w") as f:
            yaml.safe_dump({"model": {"path": "/nonexistent"}}, f)
        sdir = str(tmp_path / "state")
        os.makedirs(sdir)
        with open(os.path.join(sdir, "dep.json"), "w") as f:
            json.dump({"name": "dep", "pid": 2 ** 22 + 9,
                       "config": str(cfg_path)}, f)
        try:
            state = manager.restart("dep", state_dir=sdir)
            assert state["name"] == "dep"
            assert state["pid"] != 2 ** 22 + 9
            assert os.path.isfile(os.path.join(sdir, "dep.json"))
        finally:
            manager.stop("dep", state_dir=sdir, grace_s=2.0)
        with pytest.raises(FileNotFoundError):
            manager.restart("missing", state_dir=sdir)


# ------------------------------------------- redis drain (satellite) --
class TestRedisDrainReconnect:
    def test_drain_survives_connection_errors(self):
        from analytics_zoo_tpu.serving.redis_adapter import (
            RESULT_PREFIX, RedisFrontend)

        class FlakyOut:
            def __init__(self):
                self.failures = 2
                self.items = [("u9", {"output": np.asarray([1.0])})]

            def dequeue_all(self):
                if self.failures > 0:
                    self.failures -= 1
                    raise ConnectionError("broker gone")
                out, self.items = self.items, []
                return out

        seq0 = get_event_log().tail()[-1]["seq"]
        fe = RedisFrontend(InputQueue(), FlakyOut(), port=0)
        t = threading.Thread(target=fe._drain_loop, daemon=True)
        t.start()
        try:
            deadline = time.time() + 5
            key = f"{RESULT_PREFIX}{fe.name}:u9"
            while time.time() < deadline:
                with fe._lock:
                    if key in fe._results:
                        break
                time.sleep(0.01)
            with fe._lock:
                assert key in fe._results
                assert json.loads(fe._results[key]["value"]) == [1.0]
        finally:
            fe._stop.set()
            t.join(3.0)
            fe._server.server_close()
        assert len(_events_since(seq0, type="redis_reconnect")) == 2


# -------------------------------------------- checkpoint (satellite) --
class TestCrashSafeCheckpoint:
    def test_atomic_write_fsyncs_before_rename(self, tmp_path,
                                               monkeypatch):
        from analytics_zoo_tpu.learn import checkpoint as ckpt

        synced = []
        real_fsync = os.fsync
        monkeypatch.setattr(os, "fsync",
                            lambda fd: (synced.append(fd),
                                        real_fsync(fd))[1])
        path = str(tmp_path / "latest")
        ckpt._atomic_write(path, b"42")
        assert open(path, "rb").read() == b"42"
        assert len(synced) >= 1, "data never fsynced before rename"

    def test_failed_write_leaves_previous_checkpoint_intact(
            self, tmp_path, monkeypatch):
        """A crash mid-save (simulated at the fsync barrier) must
        leave the previous `latest` readable -- never truncated."""
        from analytics_zoo_tpu.learn import checkpoint as ckpt

        path = str(tmp_path / "latest")
        ckpt._atomic_write(path, b"step-1")

        def boom(fd):
            raise OSError("simulated power cut")

        monkeypatch.setattr(os, "fsync", boom)
        with pytest.raises(OSError):
            ckpt._atomic_write(path, b"step-2")
        monkeypatch.undo()
        assert open(path, "rb").read() == b"step-1"
