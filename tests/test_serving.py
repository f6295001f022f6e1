"""Serving end-to-end tests: queues, batcher, worker, HTTP frontend.

The analog of the reference's serving suite (ref: zoo/src/test/scala/...
/serving/ -- MockClusterServing, CorrectnessSpec full pre/post/inference
chain, FrontendActorsSpec; SURVEY.md section 4 "Serving tests with
mocks").
"""

import json
import threading
import urllib.error
import urllib.request

import flax.linen as nn
import numpy as np
import pytest

import analytics_zoo_tpu.serving as serving
from analytics_zoo_tpu.inference.inference_model import InferenceModel
from analytics_zoo_tpu.serving import (AdaptiveBatcher, DirQueue,
                                       HttpFrontend, InputQueue, MemQueue,
                                       OutputQueue, ServingWorker)


class _TinyNet(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(4)(x)


@pytest.fixture(scope="module")
def tiny_model():
    model = InferenceModel()
    module = _TinyNet()
    import jax

    variables = module.init(jax.random.PRNGKey(0), np.zeros((1, 3)))
    model.load_flax(module, variables=variables)
    return model


def test_serving_package_imports():
    # round-1 regression: serving/__init__ referenced missing modules
    for name in ("InputQueue", "OutputQueue", "DirQueue", "MemQueue",
                 "AdaptiveBatcher", "ServingWorker", "HttpFrontend",
                 "Timer"):
        assert hasattr(serving, name)


def test_mem_queue_roundtrip():
    q = InputQueue(backend="memory")
    out = OutputQueue(queue=q.queue)
    assert q.enqueue("a", x=np.arange(3.0))
    uri, tensors = out.dequeue(timeout=1)
    assert uri == "a"
    np.testing.assert_array_equal(tensors["x"], np.arange(3.0))


def test_mem_queue_backpressure():
    q = InputQueue(backend="memory", maxlen=2)
    assert q.enqueue("a", x=np.zeros(1))
    assert q.enqueue("b", x=np.zeros(1))
    assert not q.enqueue("c", x=np.zeros(1))  # full -> False


def test_dir_queue_concurrent_consumers(tmp_path):
    """Two consumers racing on one DirQueue: every item claimed exactly
    once (the atomic-rename contract replacing Redis consumer groups)."""
    path = str(tmp_path / "spool")
    q = DirQueue(path)
    n = 40
    for i in range(n):
        InputQueue(queue=q).enqueue(f"item-{i}", x=np.asarray([float(i)]))

    claimed, lock = [], threading.Lock()

    def consume():
        out = OutputQueue(queue=DirQueue(path))
        while True:
            item = out.dequeue(timeout=0.2)
            if item is None:
                return
            with lock:
                claimed.append(item[0])

    threads = [threading.Thread(target=consume) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert sorted(claimed) == sorted(f"item-{i}" for i in range(n))
    assert len(q) == 0


def test_micro_batcher_groups_and_timeout():
    """The fixed policy: no growth, linger floor == linger -- close at
    batch_size or timeout_ms after the first item."""
    q = MemQueue()
    for i in range(5):
        q.put(bytes([i]))
    b = AdaptiveBatcher(q, batch_size=3, timeout_ms=50, min_timeout_ms=50,
                        max_batch_size=3)
    assert len(b.next_batch()) == 3
    assert len(b.next_batch()) == 2
    assert b.next_batch(wait_timeout=0.01) == []
    stats = b.stats()
    assert (stats["close_size"], stats["close_deadline"]) == (1, 1)
    assert stats["last_cap"] == 3 and stats["last_linger_ms"] == 50


def test_worker_end_to_end_dirqueue(tmp_path, tiny_model):
    """enqueue -> worker batch/predict -> dequeue, results match a direct
    predict call (the CorrectnessSpec analog)."""
    in_q = InputQueue(path=str(tmp_path / "in"))
    out_q = OutputQueue(path=str(tmp_path / "out"))
    rng = np.random.RandomState(0)
    xs = {f"req-{i}": rng.randn(3).astype(np.float32) for i in range(10)}
    for uri, x in xs.items():
        assert in_q.enqueue(uri, x=x)

    worker = ServingWorker(tiny_model, in_q, out_q, batch_size=4,
                           timeout_ms=20)
    served = worker.run(max_batches=10, wait_timeout=0.05)
    assert served == 10

    results = dict(out_q.dequeue_all())
    assert sorted(results) == sorted(xs)
    direct = tiny_model.predict(np.stack(list(xs.values())))
    for i, uri in enumerate(xs):
        np.testing.assert_allclose(results[uri]["output"], direct[i],
                                   rtol=1e-5)
    stats = worker.metrics()["stages"]
    assert stats["predict_dispatch"]["count"] >= 1
    assert stats["predict_fetch"]["count"] >= 1


def test_worker_top_n(tiny_model):
    in_q, out_q = InputQueue(), OutputQueue()
    in_q.enqueue("r", x=np.ones(3, np.float32))
    worker = ServingWorker(tiny_model, in_q, out_q, top_n=2)
    worker.run(max_batches=1)
    uri, tensors = out_q.dequeue(timeout=1)
    assert tensors["classes"].shape == (2,)
    assert tensors["scores"][0] >= tensors["scores"][1]


def test_worker_survives_model_error():
    class Broken:
        def predict(self, x):
            raise RuntimeError("boom")

    in_q, out_q = InputQueue(), OutputQueue()
    in_q.enqueue("bad", x=np.ones(3, np.float32))
    worker = ServingWorker(Broken(), in_q, out_q)
    worker.run(max_batches=1)
    uri, tensors = out_q.dequeue(timeout=1)
    from analytics_zoo_tpu.serving.worker import ERROR_KEY

    assert uri == "bad" and "boom" in str(tensors[ERROR_KEY])


def test_worker_survives_bad_input_fn(tiny_model):
    """input_fn raising must not kill the loop (review finding: only
    predict was guarded)."""
    in_q, out_q = InputQueue(), OutputQueue()
    in_q.enqueue("r1", x=np.ones(3, np.float32))
    worker = ServingWorker(tiny_model, in_q, out_q,
                           input_fn=lambda t: 1 / 0)
    worker.run(max_batches=1)
    from analytics_zoo_tpu.serving.worker import ERROR_KEY

    uri, tensors = out_q.dequeue(timeout=1)
    assert uri == "r1" and ERROR_KEY in tensors
    # loop still alive: a good request after the bad one succeeds
    in_q.enqueue("r2", x=np.ones(3, np.float32))
    worker.input_fn = lambda t: next(iter(t.values()))
    worker.run(max_batches=1)
    uri, tensors = out_q.dequeue(timeout=1)
    assert uri == "r2" and "output" in tensors


@pytest.fixture()
def http_stack(tiny_model):
    in_q, out_q = InputQueue(maxlen=64), OutputQueue()
    worker = ServingWorker(tiny_model, in_q, out_q, batch_size=8,
                           timeout_ms=5).start()
    frontend = HttpFrontend(in_q, out_q, worker=worker,
                            request_timeout=15).start()
    yield frontend
    frontend.stop()
    worker.stop()


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=20) as resp:
        return resp.status, json.loads(resp.read())


def test_http_predict_and_metrics(http_stack, tiny_model):
    x = [0.5, -1.0, 2.0]
    status, body = _post(http_stack.address + "/predict",
                         {"inputs": {"x": x}})
    assert status == 200
    direct = tiny_model.predict(np.asarray([x], np.float32))[0]
    np.testing.assert_allclose(body["predictions"]["output"], direct,
                               rtol=1e-4)

    status, body = _post(http_stack.address + "/predict",
                         {"instances": [{"x": x}, {"x": x}]})
    assert status == 200 and len(body["predictions"]) == 2

    # JSON snapshot API (the pre-ISSUE-2 /metrics dict moved here)
    with urllib.request.urlopen(http_stack.address + "/metrics.json",
                                timeout=10) as resp:
        metrics = json.loads(resp.read())
    assert metrics["worker"]["served"] >= 3
    assert "predict_request" in metrics["frontend"]
    assert metrics["registry"]["zoo_serving_requests_total"]["type"] \
        == "counter"

    # /metrics is now Prometheus text exposition of the registry
    with urllib.request.urlopen(http_stack.address + "/metrics",
                                timeout=10) as resp:
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
    assert "# TYPE zoo_serving_requests_total counter" in text
    assert "zoo_serving_stage_duration_seconds_bucket" in text

    with urllib.request.urlopen(http_stack.address + "/healthz",
                                timeout=10) as resp:
        health = json.loads(resp.read())
    assert health["status"] == "ok" and health["served"] >= 3


def test_http_bad_request(http_stack):
    for bad in ({"nope": 1}, 5, [1, 2], {"instances": 3},
                {"inputs": {"x": [[1], [2, 3]]}}):
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            _post(http_stack.address + "/predict", bad)
        assert exc_info.value.code == 400, bad


class TestLauncher:
    """Config-driven deployment (ref: config.yaml +
    ClusterServingHelper)."""

    def make_model_dir(self, tmp_path):
        from analytics_zoo_tpu.models import TextClassifier

        rng = np.random.RandomState(0)
        x = rng.randint(1, 50, (64, 6)).astype(np.int32)
        y = (x[:, 0] > 25).astype(np.int32)
        m = TextClassifier(class_num=2, vocab=50, embed_dim=8,
                           sequence_length=6)
        m.fit((x, y), batch_size=32, epochs=1)
        path = str(tmp_path / "model")
        m.save_model(path)
        return path

    def test_yaml_launch_end_to_end(self, tmp_path):
        import urllib.request
        import yaml

        from analytics_zoo_tpu.serving.launcher import launch_from_yaml

        path = self.make_model_dir(tmp_path)
        # queue-client deployment: http off, results read directly
        cfg = {
            "model": {"path": path},
            "data": {"queue": "memory", "maxlen": 64},
            "params": {"batch_size": 4, "timeout_ms": 5},
            "http": {"enabled": False},
        }
        cfg_path = tmp_path / "config.yaml"
        cfg_path.write_text(yaml.safe_dump(cfg))
        app = launch_from_yaml(str(cfg_path))
        try:
            app.input_queue.enqueue(
                "r1", input=np.ones(6, np.int32))
            uri, tensors = app.output_queue.dequeue(timeout=10)
            assert uri == "r1" and "output" in tensors
            assert app.address is None
        finally:
            app.stop()

        # http deployment: the frontend owns the result stream
        cfg["http"] = {"enabled": True}
        cfg["params"]["warm_batch_sizes"] = [1, 4]
        cfg_path.write_text(yaml.safe_dump(cfg))
        app = launch_from_yaml(str(cfg_path))
        try:
            assert len(app.model._compiled) >= 2  # warmed buckets
            payload = json.dumps(
                {"inputs": {"input": [1, 2, 3, 4, 5, 6]}}).encode()
            req = urllib.request.Request(
                app.address + "/predict", data=payload,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=20) as resp:
                body = json.loads(resp.read())
            assert "predictions" in body
        finally:
            app.stop()

    def test_dir_queue_requires_path(self, tmp_path):
        from analytics_zoo_tpu.serving.launcher import launch

        path = self.make_model_dir(tmp_path)
        with pytest.raises(ValueError, match="data.path"):
            launch({"model": {"path": path},
                    "data": {"queue": "dir"}})

    def test_missing_model_path_raises(self):
        from analytics_zoo_tpu.serving.launcher import launch

        with pytest.raises(ValueError, match="model.path"):
            launch({"model": {}})


class TestCompressedImageIngestion:
    """Server-side JPEG/PNG decode (VERDICT round-3 item 4; ref:
    PreProcessing.scala:83-99 decodeImage)."""

    @staticmethod
    def _jpeg_bytes(h=32, w=32, seed=0):
        import io

        from PIL import Image

        rng = np.random.RandomState(seed)
        img = Image.fromarray(rng.randint(0, 255, (h, w, 3), np.uint8))
        buf = io.BytesIO()
        img.save(buf, format="JPEG", quality=90)
        return buf.getvalue()

    def test_decode_image_tensors_jpeg_and_png(self):
        import io

        from PIL import Image

        from analytics_zoo_tpu.serving.worker import decode_image_tensors

        raw = self._jpeg_bytes()
        t = decode_image_tensors(
            {"image": np.frombuffer(raw, np.uint8),
             "meta": np.asarray([1.0, 2.0], np.float32)})
        assert t["image"].shape == (32, 32, 3)
        assert t["image"].dtype == np.uint8
        assert t["meta"].tolist() == [1.0, 2.0]
        # PNG round-trips losslessly
        arr = np.random.RandomState(1).randint(0, 255, (8, 8, 3),
                                               np.uint8)
        buf = io.BytesIO()
        Image.fromarray(arr).save(buf, format="PNG")
        t2 = decode_image_tensors(
            {"x": np.frombuffer(buf.getvalue(), np.uint8)})
        np.testing.assert_array_equal(t2["x"], arr)

    def test_plain_uint8_vectors_pass_through(self):
        from analytics_zoo_tpu.serving.worker import decode_image_tensors

        v = np.arange(16, dtype=np.uint8)
        out = decode_image_tensors({"v": v})
        np.testing.assert_array_equal(out["v"], v)

    def test_enqueue_image_roundtrip_through_worker(self):
        from analytics_zoo_tpu.serving.queues import (
            InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.worker import ServingWorker

        class MeanModel:
            def predict(self, x):
                # x: [N, H, W, 3] uint8 stacked by the worker
                assert x.dtype == np.uint8 and x.ndim == 4
                return x.astype(np.float32).mean(axis=(1, 2, 3))

        in_q, out_q = InputQueue(), OutputQueue()
        worker = ServingWorker(MeanModel(), in_q, out_q, batch_size=4)
        raw = self._jpeg_bytes(seed=3)
        assert in_q.enqueue_image("req-1", raw)
        assert worker.run(max_batches=1, wait_timeout=0.5) == 1
        uri, result = out_q.dequeue(timeout=2.0)
        assert uri == "req-1"
        from PIL import Image
        import io as _io

        want = np.asarray(Image.open(_io.BytesIO(raw)).convert("RGB"),
                          np.float32).mean()
        np.testing.assert_allclose(float(result["output"]), want,
                                   rtol=1e-5)

    def test_http_b64_image(self):
        import base64

        from analytics_zoo_tpu.serving.http_frontend import HttpFrontend

        raw = self._jpeg_bytes(seed=4)
        fe = HttpFrontend.__new__(HttpFrontend)  # only _as_tensor
        t = fe._as_tensor({"b64": base64.b64encode(raw).decode()})
        assert t.dtype == np.uint8
        np.testing.assert_array_equal(t, np.frombuffer(raw, np.uint8))
        # non-b64 dicts and plain lists behave as before
        np.testing.assert_array_equal(fe._as_tensor([1, 2]),
                                      np.asarray([1, 2]))

    def test_corrupt_image_errors_request_not_worker(self):
        from analytics_zoo_tpu.serving.queues import (
            InputQueue, OutputQueue)
        from analytics_zoo_tpu.serving.worker import (
            ERROR_KEY, ServingWorker)

        class MeanModel:
            def predict(self, x):
                return x.astype(np.float32).mean(axis=(1, 2, 3))

        in_q, out_q = InputQueue(), OutputQueue()
        worker = ServingWorker(MeanModel(), in_q, out_q, batch_size=4)
        # JPEG magic followed by garbage: sniffer matches, decode fails
        corrupt = np.frombuffer(b"\xff\xd8\xff" + b"junk" * 8, np.uint8)
        good = self._jpeg_bytes(seed=9)
        assert in_q.enqueue("bad-1", image=corrupt)
        assert in_q.enqueue_image("good-1", good)
        assert worker.run(max_batches=1, wait_timeout=0.5) == 2
        results = {}
        for _ in range(2):
            item = out_q.dequeue(timeout=2.0)
            assert item is not None
            results[item[0]] = item[1]
        assert ERROR_KEY in results["bad-1"]
        assert "decode failed" in str(results["bad-1"][ERROR_KEY])
        assert ERROR_KEY not in results["good-1"]  # worker kept serving
