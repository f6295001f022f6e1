"""Serving fleet (ISSUE-9): stream sharding via consumer groups,
pending-entry reclaim, drain, the front-tier router, autoscaler
hysteresis, and the replicated-process fleet end to end (replica-kill
failover exactly-once, rolling restart at >= N-1 capacity)."""

import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from analytics_zoo_tpu.serving.fleet import (
    Autoscaler, FleetController, FleetRouter, Replica)
from analytics_zoo_tpu.serving.queues import (
    InputQueue, MemQueue, OutputQueue, _decode, _encode)
from analytics_zoo_tpu.serving.redis_adapter import (
    RedisFrontend, RedisStreamQueue, StreamStore)


# ------------------------------------------------------ stream store --
class TestStreamStore:
    def test_group_shards_without_duplicates(self):
        s = StreamStore()
        for i in range(10):
            assert s.xadd("st", {b"blob": b"x%d" % i}) is not None
        s.create_group("st", "g")
        a = s.xreadgroup("st", "g", "c1", 4)
        b = s.xreadgroup("st", "g", "c2", 4)
        got = [f[b"blob"] for _, f in a] + [f[b"blob"] for _, f in b]
        assert len(got) == len(set(got)) == 8

    def test_ack_trims_fully_acked_entries(self):
        s = StreamStore()
        ids = [s.xadd("st", {b"blob": b"%d" % i}) for i in range(4)]
        s.create_group("st", "g")
        s.xreadgroup("st", "g", "c1", 4)
        assert s.xlen("st") == 4
        s.xack("st", "g", ids[:2])
        assert s.xlen("st") == 2  # eager trim: xlen == outstanding

    def test_autoclaim_reclaims_idle_pending(self):
        s = StreamStore()
        for i in range(3):
            s.xadd("st", {b"blob": b"%d" % i})
        s.create_group("st", "g")
        claimed = s.xreadgroup("st", "g", "dead", 3)
        assert len(claimed) == 3
        # not idle yet: nothing reclaimable
        assert s.xautoclaim("st", "g", "alive", 10_000, 10) == []
        time.sleep(0.05)
        again = s.xautoclaim("st", "g", "alive", 10, 10)
        assert [f[b"blob"] for _, f in again] == [b"0", b"1", b"2"]
        # reassigned: pending now belongs to "alive", delivery count 2
        pend = s.xpending_range("st", "g", 10)
        assert all(c == "alive" and n == 2 for _, c, _idle, n in pend)

    def test_backlog_excludes_delivered(self):
        s = StreamStore()
        for i in range(5):
            s.xadd("st", {b"blob": b"%d" % i})
        s.create_group("st", "g")
        s.xreadgroup("st", "g", "c1", 2)
        assert s.backlog("st", "g") == 3
        assert s.xlen("st") == 5  # claims still outstanding

    def test_maxlen_backpressure(self):
        s = StreamStore(maxlen=2)
        assert s.xadd("st", {b"b": b"1"}) is not None
        assert s.xadd("st", {b"b": b"2"}) is not None
        assert s.xadd("st", {b"b": b"3"}) is None

    def test_busygroup(self):
        s = StreamStore()
        assert s.create_group("st", "g") is True
        assert s.create_group("st", "g") is False

    def test_pinned_acked_entries_leave_outstanding_count(self):
        """One stuck head entry must not inflate xlen into -OOM
        backpressure: acked-but-pinned entries are excluded."""
        s = StreamStore(maxlen=4)
        ids = [s.xadd("st", {b"b": b"%d" % i}) for i in range(4)]
        s.create_group("st", "g")
        s.xreadgroup("st", "g", "c", 4)
        s.xack("st", "g", ids[1:])  # head un-acked, rest done
        assert s.xlen("st") == 1
        # stored count is at maxlen, but outstanding is 1: no OOM
        assert s.xadd("st", {b"b": b"new"}) is not None
        s.xack("st", "g", ids[:1])  # head acked -> run trims
        assert s.xlen("st") == 1  # only the new undelivered entry

    def test_poisoned_entry_not_reclaimed(self):
        """An entry at the delivery cap stops being reclaimable and is
        evicted to the dead-letter path instead of crash-looping the
        fleet."""
        from analytics_zoo_tpu.serving.redis_adapter import (
            POISON_MAX_DELIVERIES)

        s = StreamStore()
        s.xadd("st", {b"blob": b"poison"})
        s.create_group("st", "g")
        assert len(s.xreadgroup("st", "g", "c1", 1)) == 1
        for i in range(POISON_MAX_DELIVERIES - 1):
            time.sleep(0.02)
            assert len(s.xautoclaim("st", "g", f"c{i}", 10, 1)) == 1
        time.sleep(0.02)
        assert s.xautoclaim("st", "g", "cx", 10, 1) == []  # capped
        evicted = s.evict_poisoned("st", "g", 10)
        assert [f[b"blob"] for _, f in evicted] == [b"poison"]
        assert s.xlen("st") == 0  # gone from the stream too


# ---------------------------------------------------- stream client --
@pytest.fixture()
def broker():
    fe = RedisFrontend(host="127.0.0.1", port=0).serve()
    yield fe
    fe.stop()


class TestRedisStreamQueue:
    def test_group_sharding_and_ack(self, broker):
        addr = f"{broker.host}:{broker.port}"
        prod = RedisStreamQueue(addr)
        for i in range(6):
            assert prod.put(_encode(f"u{i}", {"x": np.ones(2)}))
        c1 = RedisStreamQueue(addr, group="g", consumer="c1",
                              reclaim_idle_ms=60_000)
        c2 = RedisStreamQueue(addr, group="g", consumer="c2",
                              reclaim_idle_ms=60_000)
        u1 = [_decode(b)[0] for b in c1.get_many(3)]
        u2 = [_decode(b)[0] for b in c2.get_many(3)]
        assert not set(u1) & set(u2) and len(u1 + u2) == 6
        c1.ack_uris(u1)
        c2.ack_uris(u2)
        assert len(c1) == 0  # everything acked -> trimmed

    def test_dead_consumer_claims_reclaimed(self, broker):
        """The ISSUE-9 satellite bug: a message claimed by a crashed
        group member must NOT be orphaned -- a survivor reclaims it
        after the idle threshold."""
        addr = f"{broker.host}:{broker.port}"
        prod = RedisStreamQueue(addr)
        prod.put(_encode("victim", {"x": np.ones(2)}))
        dead = RedisStreamQueue(addr, group="g", consumer="dead",
                                reclaim_idle_ms=100)
        assert len(dead.get_many(1)) == 1  # claimed, never acked
        alive = RedisStreamQueue(addr, group="g", consumer="alive",
                                 reclaim_idle_ms=100)
        assert alive.get_many(1) == []  # not idle yet
        time.sleep(0.15)
        blobs = alive.get_many(1)
        assert [_decode(b)[0] for b in blobs] == ["victim"]
        alive.ack_uris(["victim"])
        assert len(alive) == 0

    def test_pause_stops_claiming(self, broker):
        addr = f"{broker.host}:{broker.port}"
        RedisStreamQueue(addr).put(_encode("u", {"x": np.ones(2)}))
        c = RedisStreamQueue(addr, group="g", consumer="c")
        c.pause()
        assert c.get(timeout=0.05) is None
        c.resume()
        assert c.get(timeout=1.0) is not None

    def test_put_backpressure_on_full_stream(self):
        fe = RedisFrontend(host="127.0.0.1", port=0, maxlen=2).serve()
        try:
            prod = RedisStreamQueue(f"{fe.host}:{fe.port}")
            assert prod.put(b"AZT1-not-checked-by-broker-1" * 2)
            assert prod.put(b"AZT1-not-checked-by-broker-2" * 2)
            assert prod.put(b"AZT1-overflow" * 2) is False
        finally:
            fe.stop()

    def test_poison_request_dead_lettered_with_error(self, broker):
        """End to end through the broker: a request whose every
        claimant 'dies' (never acks) gets ONE structured error result
        after the delivery cap -- the RequestLedger contract at fleet
        level -- instead of re-serving forever."""
        addr = f"{broker.host}:{broker.port}"
        prod = RedisStreamQueue(addr)
        assert prod.put(_encode("poison", {"x": np.ones(2)}))
        c = RedisStreamQueue(addr, group="serving", consumer="c",
                             reclaim_idle_ms=40)
        assert len(c.get_many(1)) == 1  # delivery 1, never acked
        deliveries = 1
        key = "cluster-serving_serving_stream:poison"
        deadline = time.time() + 10
        while key not in broker._results and time.time() < deadline:
            time.sleep(0.06)
            c._next_reclaim = 0.0  # force a reclaim pass
            deliveries += len(c.get_many(1))
        assert key in broker._results, "never dead-lettered"
        assert "dead-lettered" in broker._results[key]["value"]
        from analytics_zoo_tpu.serving.redis_adapter import (
            POISON_MAX_DELIVERIES)

        assert deliveries == POISON_MAX_DELIVERIES  # bounded re-serves
        assert len(c) == 0  # evicted from the stream

    def test_worker_acks_through_serving(self, broker):
        """End to end in-process: a ServingWorker on a consumer-group
        input acks exactly the requests it answered (stream drains to
        zero), results land in the broker's uri-keyed table."""
        from analytics_zoo_tpu.serving.worker import ServingWorker

        addr = f"{broker.host}:{broker.port}"

        class Model:
            def predict(self, x):
                return np.asarray(x) * 2

        in_q = InputQueue(queue=RedisStreamQueue(
            addr, group="serving", consumer="w1",
            reclaim_idle_ms=60_000))
        out_q = OutputQueue(queue=RedisStreamQueue(
            addr, stream="result_stream"))
        prod = RedisStreamQueue(addr)
        for i in range(8):
            assert prod.put(_encode(f"r{i}", {"x": np.ones(2)}))
        w = ServingWorker(Model(), in_q, out_q, batch_size=4,
                          timeout_ms=2.0)
        w.start()
        deadline = time.time() + 20
        while len(broker._results) < 8 and time.time() < deadline:
            time.sleep(0.02)
        w.stop()
        assert len(broker._results) == 8
        assert len(in_q) == 0  # every claim acked -> trimmed


# -------------------------------------------------------- autoscaler --
class TestAutoscaler:
    def make(self, **kw):
        t = [0.0]
        kw.setdefault("min_replicas", 1)
        kw.setdefault("max_replicas", 5)
        kw.setdefault("backlog_high", 10)
        kw.setdefault("backlog_low", 2)
        kw.setdefault("p99_high_ms", 500.0)
        kw.setdefault("up_consecutive", 3)
        kw.setdefault("down_consecutive", 5)
        kw.setdefault("cooldown_s", 10.0)
        a = Autoscaler(clock=lambda: t[0], **kw)
        return a, t

    def test_scale_up_needs_consecutive_breaches(self):
        a, t = self.make()
        assert a.decide(2, backlog=50) == 0
        assert a.decide(2, backlog=50) == 0
        assert a.decide(2, backlog=50) == 1  # 3rd in a row

    def test_oscillating_load_never_flaps(self):
        """The hysteresis property the satellite asks for: load that
        alternates across the marks moves nothing, ever."""
        a, t = self.make()
        for i in range(60):
            t[0] += 1.0
            backlog = 50 if i % 2 == 0 else 0
            assert a.decide(2, backlog=backlog) == 0

    def test_dead_band_resets_streaks(self):
        a, t = self.make()
        a.decide(2, backlog=50)
        a.decide(2, backlog=50)
        a.decide(2, backlog=5)   # between low and high: dead band
        assert a.decide(2, backlog=50) == 0  # streak restarted
        assert a.decide(2, backlog=50) == 0
        assert a.decide(2, backlog=50) == 1

    def test_scale_down_after_sustained_low(self):
        a, t = self.make()
        for _ in range(4):
            assert a.decide(3, backlog=0) == 0
        assert a.decide(3, backlog=0) == -1

    def test_bounds_clamp(self):
        a, t = self.make()
        for _ in range(10):
            assert a.decide(5, backlog=100) == 0  # at max
        b, _ = self.make()
        for _ in range(10):
            assert b.decide(1, backlog=0) == 0  # at min

    def test_cooldown_blocks_back_to_back_actions(self):
        a, t = self.make()
        for _ in range(2):
            a.decide(2, backlog=50)
        assert a.decide(2, backlog=50) == 1
        for _ in range(6):
            assert a.decide(3, backlog=50) == 0  # cooling down
        t[0] += 11.0
        # overload persisted through the whole cooldown: the streak
        # is long since earned, so the first post-cooldown sample acts
        assert a.decide(3, backlog=50) == 1

    def test_p99_breach_counts_as_overload(self):
        a, t = self.make()
        for _ in range(2):
            a.decide(2, backlog=0, p99_ms=900.0)
        assert a.decide(2, backlog=0, p99_ms=900.0) == 1


# ------------------------------------------------------------ router --
def _stub_replica(code=200, body=None):
    """A fake replica frontend: answers /predict and /healthz."""
    payload = json.dumps(body or {"predictions": [1.0]}).encode()

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

        def _send(self, c, b):
            self.send_response(c)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(b)))
            self.end_headers()
            self.wfile.write(b)

        def do_POST(self):
            self.rfile.read(
                int(self.headers.get("Content-Length", 0)))
            srv.hits += 1
            self._send(code, payload)

        def do_GET(self):
            self._send(200, b'{"status": "ok"}')

    srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    srv.hits = 0
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _fake_fleet(tmp_path, addresses):
    """A FleetController that never spawned anything: replicas are
    hand-built records pointing at stub servers (or dead ports)."""
    fc = FleetController({}, replicas=0, work_dir=str(tmp_path))
    for i, addr in enumerate(addresses):
        rep = Replica(f"r{i}", "", "", "")
        rep.address = addr
        rep.state = "up"
        rep.healthy = True
        fc._replicas[rep.name] = rep
    return fc


def _dead_address():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    return f"http://127.0.0.1:{port}"


def _post(url, payload=b"{}"):
    req = urllib.request.Request(
        url, data=payload, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, json.loads(resp.read())


class TestFleetRouter:
    def test_routes_only_to_healthy_replicas(self, tmp_path):
        good = _stub_replica()
        try:
            fc = _fake_fleet(tmp_path, [good_addr(good),
                                        _dead_address()])
            fc._replicas["r1"].healthy = False  # health check failed
            router = FleetRouter(fc, retries=0).start()
            try:
                for _ in range(5):
                    code, body = _post(router.address + "/predict")
                    assert code == 200 and "predictions" in body
                assert good.hits == 5
            finally:
                router.stop()
        finally:
            good.shutdown()

    def test_skips_quiesced_replica(self, tmp_path):
        a, b = _stub_replica(), _stub_replica()
        try:
            fc = _fake_fleet(tmp_path, [good_addr(a), good_addr(b)])
            fc._replicas["r0"].quiesced = True  # drain prelude
            router = FleetRouter(fc, retries=0).start()
            try:
                for _ in range(4):
                    assert _post(router.address + "/predict")[0] == 200
                assert a.hits == 0 and b.hits == 4
            finally:
                router.stop()
        finally:
            a.shutdown()
            b.shutdown()

    def test_retries_dead_replica_exactly_once(self, tmp_path):
        good = _stub_replica()
        try:
            fc = _fake_fleet(tmp_path, [_dead_address(),
                                        good_addr(good)])
            router = FleetRouter(fc, retries=1).start()
            try:
                # whichever round-robin pick hits the dead replica,
                # the one retry lands on the live one -- clients see
                # only 200s, and the dead replica is marked unhealthy
                for _ in range(6):
                    assert _post(router.address + "/predict")[0] == 200
                assert not fc._replicas["r0"].healthy
                assert good.hits == 6
            finally:
                router.stop()
        finally:
            good.shutdown()

    def test_all_dead_gives_structured_503(self, tmp_path):
        from analytics_zoo_tpu.serving.protocol import REPLICA_PREFIX

        fc = _fake_fleet(tmp_path, [_dead_address()])
        router = FleetRouter(fc, retries=1).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                _post(router.address + "/predict")
            assert exc_info.value.code == 503
            body = json.loads(exc_info.value.read())
            assert body["error"] == REPLICA_PREFIX
        finally:
            router.stop()

    def test_healthz_reflects_fleet(self, tmp_path):
        fc = _fake_fleet(tmp_path, ["http://127.0.0.1:1"])
        router = FleetRouter(fc, retries=0).start()
        try:
            code, body = _get_json(router.address + "/healthz")
            assert code == 200 and body["replicas"]["healthy"] == 1
            fc._replicas["r0"].healthy = False
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                _get_json(router.address + "/healthz")
            assert exc_info.value.code == 503
        finally:
            router.stop()


def good_addr(srv):
    host, port = srv.server_address[:2]
    return f"http://{host}:{port}"


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, json.loads(resp.read())


# ------------------------------------------------------------- drain --
class _SlowModel:
    def __init__(self, delay_s):
        self.delay_s = delay_s

    def predict(self, x):
        time.sleep(self.delay_s)
        return np.asarray(x)


class TestDrain:
    def _worker(self, delay_s, n, batch_size=4):
        from analytics_zoo_tpu.serving.worker import ServingWorker

        in_q = InputQueue(queue=MemQueue())
        out_q = OutputQueue(queue=MemQueue())
        for i in range(n):
            assert in_q.enqueue(f"d{i}", x=np.ones(2, np.float32))
        w = ServingWorker(_SlowModel(delay_s), in_q, out_q,
                          batch_size=batch_size, timeout_ms=1.0)
        return w, in_q, out_q

    def test_drain_completes_within_deadline(self):
        w, in_q, out_q = self._worker(delay_s=0.01, n=12)
        w.start()
        time.sleep(0.2)  # let it pull some work
        assert w.drain(deadline_s=20.0) is True
        assert w._thread is None  # run exited cleanly
        # everything pulled before the drain flag was answered; the
        # rest is still on the input queue (never lost)
        answered = len(out_q.dequeue_all())
        assert answered + len(in_q) == 12
        assert answered == w.served

    def test_drain_deadline_expires_with_slow_inflight(self):
        w, in_q, out_q = self._worker(delay_s=1.5, n=4, batch_size=1)
        w.start()
        time.sleep(0.2)  # a 1.5 s predict is now in flight
        t0 = time.monotonic()
        assert w.drain(deadline_s=0.3) is False
        assert time.monotonic() - t0 < 1.0  # gave up at the deadline
        w.stop(join_timeout=10.0)

    def test_draining_frontend_refuses_and_fails_health(self):
        from analytics_zoo_tpu.serving.http_frontend import HttpFrontend
        from analytics_zoo_tpu.serving.protocol import DRAINING_PREFIX

        in_q = InputQueue(queue=MemQueue())
        out_q = OutputQueue(queue=MemQueue())
        fe = HttpFrontend(in_q, out_q).start()
        try:
            assert _get_json(fe.address + "/healthz")[0] == 200
            fe.set_draining()
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                _get_json(fe.address + "/healthz")
            assert exc_info.value.code == 503
            assert json.loads(exc_info.value.read())["status"] == (
                "draining")
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                _post(fe.address + "/predict",
                      json.dumps({"inputs": {"x": [1.0]}}).encode())
            assert exc_info.value.code == 503
            body = json.loads(exc_info.value.read())
            assert body["error"] == DRAINING_PREFIX
            assert exc_info.value.headers.get("Retry-After")
        finally:
            fe.stop()


# ---------------------------------------------------- manager --json --
class TestManagerStatusJson:
    def _run(self, state_dir, *extra):
        import subprocess
        import sys

        return subprocess.run(
            [sys.executable, "-m", "analytics_zoo_tpu.serving.manager",
             "status", "--json", "--state-dir", str(state_dir),
             *extra],
            capture_output=True, text=True)

    def test_alive_deployment_exits_zero(self, tmp_path):
        # our own pid, no recorded identity -> legacy liveness: alive
        with open(tmp_path / "dep.json", "w") as f:
            json.dump({"name": "dep", "pid": os.getpid()}, f)
        r = self._run(tmp_path)
        assert r.returncode == 0, r.stdout
        out = json.loads(r.stdout)
        assert out["alive"] == out["total"] == 1
        assert out["deployments"][0]["running"] is True

    def test_dead_deployment_exits_one(self, tmp_path):
        with open(tmp_path / "dep.json", "w") as f:
            json.dump({"name": "dep", "pid": 2 ** 22 + 12345}, f)
        r = self._run(tmp_path)
        assert r.returncode == 1, r.stdout
        out = json.loads(r.stdout)
        assert out["alive"] == 0 and out["total"] == 1

    def test_nothing_tracked_exits_one(self, tmp_path):
        r = self._run(tmp_path)
        assert r.returncode == 1
        assert json.loads(r.stdout)["total"] == 0


# ------------------------------------------------------ fleet e2e ----
@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    """A saved ZooModel the replica launcher processes load."""
    from analytics_zoo_tpu.models import TextClassifier

    rng = np.random.RandomState(0)
    x = rng.randint(1, 50, (64, 6)).astype(np.int32)
    y = (x[:, 0] > 25).astype(np.int32)
    m = TextClassifier(class_num=2, vocab=50, embed_dim=8,
                       sequence_length=6)
    m.fit((x, y), batch_size=32, epochs=1)
    path = str(tmp_path_factory.mktemp("fleet") / "model")
    m.save_model(path)
    return path


def _fleet_env():
    # replicas are plain CPU processes: drop the 8-virtual-device
    # forcing (test_multiprocess convention) and tighten the reclaim
    # threshold so kill-failover resolves inside the test budget
    env = {"JAX_PLATFORMS": "cpu",
           "AZT_ZOO_SERVING_FLEET_RECLAIM_IDLE_MS": "1000",
           "AZT_ZOO_SERVING_DRAIN_DEADLINE_MS": "10000"}
    return env


class TestFleetEndToEnd:
    def test_kill_failover_and_rolling_restart(self, model_dir,
                                               tmp_path):
        """One fleet, two drills (startup paid once): (1) SIGKILL a
        replica mid-run on a 3-replica fleet -> every stream request
        answered exactly once; (2) rolling restart under live router
        traffic -> zero 5xx and observed capacity >= N-1."""
        from analytics_zoo_tpu.serving import chaos

        answered = {}
        injector = chaos.install(chaos.ChaosInjector(
            chaos.parse_spec("kill:replica:at=30"), seed=0))
        fc = FleetController(
            {"model": {"path": model_dir},
             "params": {"batch_size": 4, "timeout_ms": 2,
                        "warm_batch_sizes": [1, 4]}},
            replicas=3, work_dir=str(tmp_path / "fleet"),
            env=_fleet_env(), seed=0, poll_interval_s=0.2,
            health_interval_s=0.4,
            on_result=lambda uri, t: answered.__setitem__(
                uri, answered.get(uri, 0) + 1))
        fc.start()
        try:
            assert fc.wait_healthy(3, timeout_s=300), (
                fc.replica_states())

            # ---- drill 1: replica SIGKILL mid-run, exactly-once ----
            prod = RedisStreamQueue(fc.broker_address)
            n = 150
            for i in range(n):
                assert prod.put(
                    _encode(f"k{i:04d}", {"input": np.ones(6,
                                                           np.int32)}))
            deadline = time.time() + 120
            while len(answered) < n and time.time() < deadline:
                time.sleep(0.1)
            assert len(answered) == n, (
                f"lost {n - len(answered)} requests across the kill")
            assert all(c == 1 for c in answered.values()), {
                u: c for u, c in answered.items() if c != 1}
            assert fc.chaos_kills == 1  # the schedule really fired
            assert injector.counts().get("replica:kill") == 1

            # ---- drill 2: rolling restart under router traffic ----
            assert fc.wait_healthy(3, timeout_s=180)
            codes = {}
            stop_load = threading.Event()

            def load():
                body = json.dumps(
                    {"inputs": {"input": [1, 2, 3, 4, 5, 6]}}).encode()
                while not stop_load.is_set():
                    try:
                        req = urllib.request.Request(
                            fc.router.address + "/predict", data=body,
                            headers={"Content-Type":
                                     "application/json"})
                        with urllib.request.urlopen(
                                req, timeout=30) as resp:
                            code = resp.status
                    except urllib.error.HTTPError as e:
                        code = e.code
                    except (urllib.error.URLError, OSError):
                        code = -1
                    codes[code] = codes.get(code, 0) + 1

            loader = threading.Thread(target=load, daemon=True)
            loader.start()
            ok = fc.rolling_restart(timeout_s=180)
            stop_load.set()
            loader.join(35.0)
            assert ok, fc.stats()
            bad = {c: k for c, k in codes.items()
                   if c >= 500 or c < 0}
            assert not bad, f"router surfaced failures: {codes}"
            assert codes.get(200, 0) > 0  # traffic really flowed
            assert fc.min_healthy_during_restart >= 2  # >= N-1
        finally:
            fc.stop()
            chaos.uninstall()
