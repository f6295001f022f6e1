"""Pipelined serving engine + adaptive micro-batching tests.

Covers the ISSUE-1 tentpole contract: stage overlap (decode of batch
k+1 while batch k is in flight), the adaptive batcher's three policy
behaviors (size close, tightened-deadline close, backlog cap growth on
the bucket ladder), and no result loss/reordering at the in-flight
cap.
"""

import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu.serving.batcher import AdaptiveBatcher
from analytics_zoo_tpu.serving.queues import InputQueue, MemQueue, OutputQueue
from analytics_zoo_tpu.serving.worker import ServingWorker


# ------------------------------------------------------------ helpers --
class _LazyResult:
    """Device-array stand-in: materializing (np.asarray) blocks until
    ``release`` is set -- models JAX async dispatch, where dispatch
    returns immediately and only the fetch waits on compute."""

    def __init__(self, value, release=None, delay=0.0):
        self._value = np.asarray(value)
        self._release = release
        self._delay = delay

    def __array__(self, dtype=None, copy=None):
        if self._release is not None:
            assert self._release.wait(timeout=30.0), "never released"
        if self._delay:
            time.sleep(self._delay)
        a = self._value
        return a.astype(dtype) if dtype is not None else a


class _AsyncEcho:
    """predict_async doubles the input, returning a lazy result."""

    def __init__(self, release=None, delay=0.0):
        self.release = release
        self.delay = delay
        self.dispatched = 0

    def predict_async(self, x):
        self.dispatched += 1
        return (_LazyResult(np.asarray(x, np.float64) * 2.0,
                            self.release, self.delay), len(x))


def _fill(n, shape=(2,)):
    in_q, out_q = InputQueue(), OutputQueue()
    for i in range(n):
        assert in_q.enqueue(f"r{i:04d}",
                            x=np.full(shape, float(i), np.float32))
    return in_q, out_q


# ------------------------------------------------------- wire codec ----
class TestWireCodec:
    def test_v2_roundtrip_edge_cases(self):
        from analytics_zoo_tpu.serving.queues import _decode_full, _encode

        cases = [("", {"x": np.zeros(0, np.float32)}),
                 ("u", {"s": np.asarray(3.5)}),
                 ("u2", {"b": np.asarray([True, False]),
                         "i": np.asarray([1, 2], np.int8)}),
                 ("req", {"t": np.asarray(["ab", "cdef"])}),
                 ("img", {"raw": np.arange(256, dtype=np.uint8)})]
        for uri, payload in cases:
            u, t, r = _decode_full(_encode(uri, payload))
            assert u == uri and r is None
            for k, v in payload.items():
                np.testing.assert_array_equal(t[k], np.asarray(v))
                assert t[k].dtype == np.asarray(v).dtype
                # strict: assert_array_equal broadcasts () vs (1,),
                # but the codec must round-trip scalar SHAPES exactly
                assert t[k].shape == np.asarray(v).shape, k
        u, t, r = _decode_full(
            _encode("a", {"x": np.ones(2)}, reply_to="stream-9"))
        assert (u, r) == ("a", "stream-9")

    def test_error_reply_string_round_trips_clean(self):
        """0-d error strings must not come back as 1-element arrays
        (str() would render \"['boom']\" in HTTP error bodies)."""
        from analytics_zoo_tpu.serving.queues import _decode_full, _encode

        _, t, _ = _decode_full(_encode("e", {"__error__":
                                             np.asarray("boom")}))
        assert t["__error__"].shape == ()
        assert str(t["__error__"]) == "boom"

    def test_non_contiguous_tensor_round_trips(self):
        from analytics_zoo_tpu.serving.queues import _decode_full, _encode

        v = np.arange(12.0).reshape(3, 4).T  # not C-contiguous
        _, t, _ = _decode_full(_encode("nc", {"x": v}))
        np.testing.assert_array_equal(t["x"], v)
        assert t["x"].shape == (4, 3)

    def test_legacy_npz_blobs_still_decode(self):
        """AZT1 is the only framing: an np.savez (zip) blob is refused
        like any other outside input that is not AZT1."""
        import io

        from analytics_zoo_tpu.serving.queues import _decode_full

        buf = io.BytesIO()
        np.savez(buf, __uri__=np.asarray("old"), x=np.arange(3))
        assert buf.getvalue()[:2] == b"PK"
        with pytest.raises(ValueError, match="AZT1"):
            _decode_full(buf.getvalue())

    def test_garbage_and_object_dtype_rejected(self):
        from analytics_zoo_tpu.serving.queues import _decode_full, _encode

        with pytest.raises(ValueError):
            _decode_full(b"garbagegarbage")
        with pytest.raises(ValueError, match="object"):
            _encode("u", {"o": np.asarray([{"a": 1}], dtype=object)})

    def test_decoded_tensors_are_writable(self):
        from analytics_zoo_tpu.serving.queues import _decode_full, _encode

        _, t, _ = _decode_full(_encode("w", {"x": np.arange(4.0)}))
        t["x"][0] = 9.0  # user hooks may mutate in place
        assert t["x"][0] == 9.0


class TestQueueBatchOps:
    def test_mem_queue_get_many_put_many(self):
        q = MemQueue(maxlen=10)
        assert q.put_many([bytes([i]) for i in range(8)]) == 8
        assert q.put_many([b"x", b"y", b"z"]) == 2  # maxlen clips
        assert q.get_many(5) == [bytes([i]) for i in range(5)]
        assert len(q.get_many(100)) == 5
        assert q.get_many(3) == []

    def test_dir_queue_get_many(self, tmp_path):
        from analytics_zoo_tpu.serving.queues import DirQueue

        q = DirQueue(str(tmp_path / "spool"))
        for i in range(6):
            q.put(bytes([i]))
        got = q.get_many(4)
        assert got == [bytes([i]) for i in range(4)]
        assert len(q) == 2


# ----------------------------------------------------- adaptive policy --
class TestAdaptiveBatcher:
    def test_size_close_at_base_cap(self):
        q = MemQueue()
        for i in range(8):
            q.put(bytes([i]))
        b = AdaptiveBatcher(q, batch_size=4, timeout_ms=50,
                            max_batch_size=4)
        assert len(b.next_batch()) == 4
        assert b.stats()["close_size"] == 1
        assert b.stats()["last_cap"] == 4

    def test_deadline_tightens_when_queue_shallow(self):
        """2 waiting requests << batch_size: the linger must shrink
        toward min_timeout_ms instead of burning the full timeout."""
        q = MemQueue()
        q.put(b"a")
        q.put(b"b")
        b = AdaptiveBatcher(q, batch_size=64, timeout_ms=500,
                            min_timeout_ms=10)
        t0 = time.monotonic()
        batch = b.next_batch()
        elapsed = time.monotonic() - t0
        assert len(batch) == 2
        # depth behind the first item was 1/63 -> linger ~= the floor;
        # anything near the full 500 ms means no tightening happened
        assert elapsed < 0.25, f"linger did not tighten: {elapsed:.3f}s"
        s = b.stats()
        assert s["close_deadline"] == 1
        assert s["last_linger_ms"] < 100

    def test_deep_queue_keeps_full_linger_budget(self):
        q = MemQueue()
        for i in range(40):
            q.put(bytes([i % 256]))
        b = AdaptiveBatcher(q, batch_size=8, timeout_ms=500,
                            min_timeout_ms=10, max_batch_size=8)
        t0 = time.monotonic()
        batch = b.next_batch()
        # items were all waiting: full batch, near-zero wait, and the
        # POLICY chose the full linger (depth covers the batch)
        assert len(batch) == 8
        assert time.monotonic() - t0 < 0.2
        assert b.stats()["last_linger_ms"] == pytest.approx(500.0)

    def test_backlog_grows_cap_on_bucket_ladder(self):
        q = MemQueue()
        for i in range(40):
            q.put(bytes([i % 256]))
        b = AdaptiveBatcher(q, batch_size=8, timeout_ms=20,
                            max_batch_size=32)
        batch = b.next_batch()
        # depth 39 behind the first item -> bucket(40)=64, clipped to
        # the max: cap 32, a power-of-two ladder value
        assert len(batch) == 32
        s = b.stats()
        assert s["last_cap"] == 32
        assert s["close_size"] == 1
        # the remaining 8 drain at base cap
        assert len(b.next_batch()) == 8

    def test_burst_tail_closes_on_size_not_linger(self):
        """Backlog growth snaps to the largest bucket the KNOWN
        backlog fills: a 20-deep burst at base 8 dispatches 16
        immediately (size close) instead of growing to 32 and
        lingering the full deadline for stragglers."""
        q = MemQueue()
        for i in range(20):
            q.put(bytes([i % 256]))
        b = AdaptiveBatcher(q, batch_size=8, timeout_ms=500,
                            min_timeout_ms=10, max_batch_size=32)
        t0 = time.monotonic()
        batch = b.next_batch()
        assert len(batch) == 16  # floor bucket of 20, not bucket(20)=32
        assert time.monotonic() - t0 < 0.2, "burst tail lingered"
        assert b.stats()["close_size"] == 1

    def test_growth_disabled_when_max_equals_base(self):
        q = MemQueue()
        for i in range(40):
            q.put(bytes([i % 256]))
        b = AdaptiveBatcher(q, batch_size=8, timeout_ms=20,
                            max_batch_size=8)
        assert len(b.next_batch()) == 8
        assert b.stats()["last_cap"] == 8

    def test_depthless_queue_falls_back_to_fixed_policy(self):
        class NoLen:
            def __init__(self):
                self._q = MemQueue()
                self.put = self._q.put

            def get(self, timeout=None):
                return self._q.get(timeout)

        q = NoLen()
        for i in range(6):
            q.put(bytes([i]))
        b = AdaptiveBatcher(q, batch_size=4, timeout_ms=20)
        assert len(b.next_batch()) == 4
        assert b.stats()["last_cap"] == 4


# ---------------------------------------------------------- pipelining --
class TestPipelinedEngine:
    def test_decode_overlaps_inflight_batch(self):
        """Decode of batch k+1 must run while batch k is still in
        flight: dispatch batch 0 whose result cannot materialize until
        released, and watch the decode-stage counter reach batch 1."""
        release = threading.Event()
        model = _AsyncEcho(release=release)
        in_q, out_q = _fill(2)
        worker = ServingWorker(model, in_q, out_q, batch_size=1,
                               timeout_ms=1.0, max_batch_size=1,
                               pipeline_depth=1)
        worker.start()
        try:
            deadline = time.time() + 10
            decoded = 0
            while time.time() < deadline:
                stages = worker.timer.summary()
                decoded = stages.get("decode", {}).get("count", 0)
                # wait for BOTH: batch 0 dispatched AND batch 1
                # decoded (decoded_q lets decode run 2 ahead before
                # the driver is ever scheduled, so decode-count alone
                # does not imply a dispatch happened yet)
                if decoded >= 2 and model.dispatched >= 1:
                    break
                time.sleep(0.005)
            # batch 0 is dispatched but NOT finalized (its fetch blocks
            # on `release`), yet batch 1 has already been decoded
            assert decoded >= 2, "decode stage never reached batch k+1"
            assert model.dispatched >= 1
            assert out_q.dequeue(timeout=0) is None  # nothing finalized
        finally:
            release.set()
            deadline = time.time() + 10
            results = {}
            while len(results) < 2 and time.time() < deadline:
                item = out_q.dequeue(timeout=0.2)
                if item is not None:
                    results[item[0]] = item[1]
            worker.stop()
        assert sorted(results) == ["r0000", "r0001"]
        np.testing.assert_allclose(results["r0001"]["output"],
                                   [2.0, 2.0])

    def test_stress_no_loss_no_reorder_at_inflight_cap(self):
        """128 requests through a depth-2 window with slow fetches:
        every request answered exactly once, in arrival order."""
        n = 128
        model = _AsyncEcho(delay=0.001)
        in_q, out_q = _fill(n)
        worker = ServingWorker(model, in_q, out_q, batch_size=4,
                               timeout_ms=2.0, max_batch_size=16,
                               pipeline_depth=2)
        worker.start()
        try:
            deadline = time.time() + 30
            results = []
            while len(results) < n and time.time() < deadline:
                item = out_q.dequeue(timeout=0.2)
                if item is not None:
                    results.append(item)
        finally:
            worker.stop()
        assert len(results) == n, f"lost {n - len(results)} results"
        uris = [u for u, _ in results]
        assert uris == sorted(uris), "results reordered"
        assert len(set(uris)) == n, "duplicated results"
        for u, tensors in results:
            i = int(u[1:])
            np.testing.assert_allclose(tensors["output"],
                                       [2.0 * i, 2.0 * i])
        assert worker.metrics()["pipeline"]["depth"] == 2

    def test_bounded_run_answers_everything_it_pulled(self):
        model = _AsyncEcho()
        in_q, out_q = _fill(10)
        worker = ServingWorker(model, in_q, out_q, batch_size=4,
                               timeout_ms=2.0)
        served = worker.run(max_batches=12, wait_timeout=0.02)
        assert served == 10
        assert len(dict(out_q.dequeue_all())) == 10

    def test_pipelined_survives_bad_input_fn_and_model_error(self):
        class Broken:
            def predict(self, x):
                raise RuntimeError("boom")

        from analytics_zoo_tpu.serving.worker import ERROR_KEY

        in_q, out_q = _fill(3)
        worker = ServingWorker(Broken(), in_q, out_q, batch_size=8,
                               timeout_ms=1.0)
        worker.run(max_batches=3, wait_timeout=0.02)
        results = dict(out_q.dequeue_all())
        assert len(results) == 3
        for tensors in results.values():
            assert "boom" in str(tensors[ERROR_KEY])

    def test_metrics_expose_pipeline_stages_and_gauges(self):
        model = _AsyncEcho()
        in_q, out_q = _fill(20)
        worker = ServingWorker(model, in_q, out_q, batch_size=4,
                               timeout_ms=2.0, max_batch_size=16)
        worker.run(max_batches=20, wait_timeout=0.02)
        m = worker.metrics()
        assert m["served"] == 20
        pipe = m["pipeline"]
        assert pipe["depth"] >= 1
        assert pipe["batcher"]["batches"] >= 1
        assert pipe["batcher"]["mean_occupancy"] > 0
        stages = m["stages"]
        for stage in ("batch_wait", "decode", "stack",
                      "predict_dispatch", "predict_fetch",
                      "postprocess", "assembly_wait", "inflight_wait",
                      "service"):
            assert stage in stages, f"missing stage {stage}"
        gauges = stages["gauges"]
        assert gauges["batch_occupancy"]["avg"] > 0
        assert "queue_depth" in gauges
        assert "inflight" in gauges
