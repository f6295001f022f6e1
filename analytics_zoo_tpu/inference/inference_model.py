"""InferenceModel: the serving-facing prediction engine.

The analog of ``InferenceModel`` (ref: zoo/.../pipeline/inference/
InferenceModel.scala:28-608, pyzoo/zoo/pipeline/inference/
inference_model.py:24-250). Key design inversion for TPU: the reference
maintains a ``LinkedBlockingQueue`` of ``concurrentNum`` model copies
because BigDL modules are stateful; XLA executables are pure + thread-safe,
so ONE AOT-compiled executable per batch-shape bucket serves any number of
threads. Batch inputs are padded up to the nearest bucket (powers of two)
to bound recompilation.

Loaders (mirroring doLoad* -- ref: InferenceModel.scala:76-260):
- ``load_zoo``         a saved ZooModel directory
- ``load_flax``        a flax module (+ variables or checkpoint dir)
- ``load_torch``       torch state_dict imported into a flax module
- ``load_encrypted_*`` AES-encrypted variants (EncryptSupportive analog)
"""

from __future__ import annotations

import io
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.common.log import get_logger
from analytics_zoo_tpu.obs.events import record_compile, warming
from analytics_zoo_tpu.obs.metrics import get_registry

logger = get_logger(__name__)

# inference engine wiring into the unified registry: live XLA compiles
# (should stay flat after warm-up -- a climbing counter means requests
# are paying compile stalls), dispatch volume, and how much of each
# device batch is bucket padding (wasted compute; high ratios mean the
# batcher's caps sit badly against the bucket ladder)
_REG = get_registry()
# compile / pad-ratio series carry (bucket, shard mode) labels so a
# sharded deployment's entries stay distinguishable from single-chip
# ones in /metrics instead of aggregating into one series (mode "off"
# = the unsharded engine; "tp"/"dp"/"tp_q8" = inference/sharded.py)
_M_COMPILES = _REG.counter(
    "zoo_inference_compile_total",
    "XLA shape-bucket compiles by (bucket, shard mode) -- flat after "
    "warm-up in a healthy deployment; climbing means requests pay "
    "compile stalls", labelnames=("bucket", "mode"))
_M_DISPATCH = _REG.counter(
    "zoo_inference_dispatch_total",
    "Prediction batches dispatched, by shard mode",
    labelnames=("mode",))
_M_PAD = _REG.histogram(
    "zoo_inference_batch_pad_ratio",
    "Fraction of each dispatched device batch that is bucket padding, "
    "by (bucket, shard mode)", labelnames=("bucket", "mode"),
    buckets=(0.0, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0))


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def bucket_ladder(max_batch: int) -> List[int]:
    """Every power-of-two batch bucket up to (and including) the one
    covering ``max_batch`` -- the shape ladder ``predict`` pads onto.
    The serving launcher warms these; the adaptive batcher snaps its
    backlog-grown caps to them so batching policy never invents an XLA
    shape."""
    ladder = []
    b = 1
    while b <= _bucket(max_batch):
        ladder.append(b)
        b *= 2
    return ladder


class InferenceModel:
    def __init__(self, concurrent_num: int = 1, dtype=None):
        # concurrent_num kept for API parity (ref: InferenceModel.scala
        # concurrentNum); XLA needs no model copies.
        self.concurrent_num = concurrent_num
        if dtype is None:
            # advisory serving dtype (importers/quantize consult it);
            # the zoo.inference.default_dtype key, bfloat16 on TPU
            from analytics_zoo_tpu.common.config import get_config

            dtype = str(get_config().get("zoo.inference.default_dtype",
                                         "bfloat16"))
        self.dtype = dtype
        from analytics_zoo_tpu.common.context import (
            enable_compilation_cache)

        enable_compilation_cache()  # serving restarts skip recompiles
        self._apply_fn: Optional[Callable] = None
        self.variables: Optional[Dict] = None
        self._compiled: Dict[Any, Callable] = {}
        self._lock = threading.Lock()
        self._quantized = False
        self.example_input = None  # set by load_zoo for warm_up
        # mesh routing (inference/sharded.py): None = single-chip, the
        # pre-mesh engine byte-for-byte (including cache keys)
        self.shard_plan = None

    # ------------------------------------------------------------ loads --
    def load_zoo(self, path: str) -> "InferenceModel":
        """(ref: doLoadBigDL / zoo model load)."""
        from analytics_zoo_tpu.models.common import ZooModel

        model = ZooModel.load_model(path)
        est = model.estimator
        adapter = est.adapter
        self._apply_fn = (
            lambda variables, x: adapter.apply(variables, x,
                                               training=False)[0])
        self.variables = est.variables
        try:  # lets deployments warm_up without knowing the model class
            self.example_input = model._example_input()
        except Exception:
            self.example_input = None
        return self

    def load_flax(self, module, variables=None,
                  checkpoint_dir: Optional[str] = None,
                  example_input=None) -> "InferenceModel":
        from analytics_zoo_tpu.learn.estimator import FlaxModelAdapter

        adapter = FlaxModelAdapter(module)
        if variables is None:
            if checkpoint_dir is None:
                raise ValueError("pass variables or checkpoint_dir")
            from analytics_zoo_tpu.learn import checkpoint as ckpt

            variables, _, _ = ckpt.load_checkpoint(checkpoint_dir, None,
                                                   None)
        self._apply_fn = (
            lambda v, x: adapter.apply(v, x, training=False)[0])
        self.variables = variables
        return self

    def load_torch(self, module, state_dict, key_map=None,
                   wrap: str = "params") -> "InferenceModel":
        """torch state_dict -> flax module weights
        (ref: doLoadPyTorch, net/TorchModel.scala -- except weights are
        imported, not executed via an embedded interpreter)."""
        from analytics_zoo_tpu.inference.importers import (
            import_torch_state_dict)

        params = import_torch_state_dict(state_dict, key_map=key_map)
        return self.load_flax(module, variables={wrap: params})

    def load_graph(self, graph_fn) -> "InferenceModel":
        """Serve an imported executable graph
        (:class:`~analytics_zoo_tpu.inference.graph_executor.GraphFunction`)
        through the bucketed-jit predict path. The execution analog of
        the reference's TFNet/ONNX serving backends
        (ref: InferenceModel.scala doLoadTensorflow -> TFNet session;
        here the graph IS a jax function, so it shares predict/warm_up/
        quantize infrastructure with native models)."""
        # float weight constants ride as "variables" so quantize() can
        # compress them and jit treats them as runtime operands; static
        # operands (shapes/axes -- integer/scalar consts) stay baked
        # into the graph so trace-time ops see concrete values
        import copy

        weights = graph_fn.weight_constants()
        self.variables = {"graph_consts": weights}
        # private copy without the fp weights: quantize() can release
        # the full-precision copies, and the CALLER's GraphFunction
        # stays intact (it must keep working standalone)
        graph_fn = copy.copy(graph_fn)
        graph_fn.constants = {k: v for k, v in graph_fn.constants.items()
                              if k not in weights}
        single = len(graph_fn.input_names) == 1

        def apply_graph(variables, x):
            feed = (x if isinstance(x, dict)
                    else {graph_fn.input_names[0]: x} if single
                    else dict(zip(graph_fn.input_names, x)))
            return graph_fn.execute(feed,
                                    constants=variables["graph_consts"])

        self._apply_fn = apply_graph
        return self

    def load_tf_graph(self, path_or_bytes, inputs=None, outputs=None
                      ) -> "InferenceModel":
        """Frozen TF GraphDef -> executable serving model
        (ref: doLoadTensorflow frozen path, TFNet.scala:56-719)."""
        from analytics_zoo_tpu.inference.graph_executor import (
            load_tf_frozen_graph)

        return self.load_graph(load_tf_frozen_graph(
            path_or_bytes, inputs=inputs, outputs=outputs))

    def load_onnx(self, path_or_bytes) -> "InferenceModel":
        """ONNX model -> executable serving model
        (ref: onnx_loader.py:32-128)."""
        from analytics_zoo_tpu.inference.graph_executor import (
            load_onnx_model)

        return self.load_graph(load_onnx_model(path_or_bytes))

    def load_encrypted_zoo(self, path: str, secret: str,
                           ) -> "InferenceModel":
        """Directory of encrypted files produced by ``save_encrypted``
        (ref: doLoadEncrypted*, EncryptSupportive.scala)."""
        import os
        import tempfile

        from analytics_zoo_tpu.inference.encrypt import decrypt_bytes

        with tempfile.TemporaryDirectory() as tmp:
            for name in os.listdir(path):
                with open(os.path.join(path, name), "rb") as f:
                    blob = f.read()
                with open(os.path.join(tmp, name), "wb") as f:
                    f.write(decrypt_bytes(blob, secret))
            return self.load_zoo(tmp)

    @staticmethod
    def save_encrypted(model_dir: str, out_dir: str, secret: str) -> None:
        """Encrypt every file of a saved model directory."""
        import os

        from analytics_zoo_tpu.inference.encrypt import encrypt_bytes

        os.makedirs(out_dir, exist_ok=True)
        for name in os.listdir(model_dir):
            src = os.path.join(model_dir, name)
            if not os.path.isfile(src):
                continue
            with open(src, "rb") as f:
                blob = encrypt_bytes(f.read(), secret)
            with open(os.path.join(out_dir, name), "wb") as f:
                f.write(blob)

    # --------------------------------------------------------- quantize --
    def quantize(self, min_size: int = 1024) -> "InferenceModel":
        """Weight-only int8 (ref: int8/OpenVINO VNNI path). Weights are
        stored int8; the forward dequantizes (XLA fuses the rescale)."""
        from analytics_zoo_tpu.inference.quantize import (
            dequantize_params, quantize_params)

        if self.variables is None:
            raise RuntimeError("load a model before quantize()")
        if self.shard_plan is not None:
            raise RuntimeError("quantize() before shard(): weight-only "
                               "quantization rebuilds the variable "
                               "tree the plan committed to its mesh")
        q_tree, scales = quantize_params(self.variables, min_size)
        inner = self._apply_fn

        def apply_q(variables, x):
            return inner(dequantize_params(variables, scales), x)

        self._apply_fn = apply_q
        self.variables = q_tree
        self._compiled.clear()
        self._quantized = True
        return self

    # ------------------------------------------------------------ shard --
    def shard(self, plan="config") -> "InferenceModel":
        """Route prediction through a device mesh
        (:mod:`analytics_zoo_tpu.inference.sharded`). ``plan="config"``
        resolves ``zoo.serving.shard.*``; pass a :class:`ShardPlan` to
        pick the mesh explicitly, or None for a no-op. Attach AFTER
        ``quantize()`` (weight-only int8 replaces the variable tree) and
        before ``warm_up`` so the ladder compiles under the active
        mesh. Attaching commits the variables onto the mesh; the bucket
        cache keeps any pre-attach entries -- their keys cannot collide
        with the plan-signed ones."""
        if plan == "config":
            from analytics_zoo_tpu.inference.sharded import (
                resolve_shard_plan)

            plan = resolve_shard_plan(self.variables)
        if plan is None:
            return self
        if self.variables is None:
            raise RuntimeError("load a model before shard()")
        if self.shard_plan is not None:
            raise RuntimeError(
                "a shard plan is already attached; build a fresh "
                "InferenceModel to re-shard (variables are committed "
                "to the previous mesh)")
        self.variables = plan.place_variables(self.variables)
        self.shard_plan = plan
        return self

    def _bucket_for(self, n: int) -> int:
        """The device-batch bucket covering ``n``: the power-of-two
        ladder single-chip; under a batch-splitting shard plan the same
        ladder in units of the mesh size (every bucket divides evenly
        across the devices -- and re-bucketing a bucket is a fixed
        point, so warmed sizes stay warmed)."""
        plan = self.shard_plan
        m = plan.batch_multiple if plan is not None else 1
        if m <= 1:
            return _bucket(n)
        return m * _bucket(-(-n // m))

    # ---------------------------------------------------------- warm-up --
    def warm_up(self, example_input,
                batch_sizes: Sequence[int] = (1, 8, 32)
                ) -> "InferenceModel":
        """Pre-compile the shape buckets a serving deployment will hit
        (SURVEY.md section 7 step 7: AOT-compile per batch-shape), so the
        first real request never pays the XLA compile. ``example_input``
        is a single-sample (or any-size) batch pytree; each requested
        batch size compiles its power-of-two bucket."""
        if self._apply_fn is None:
            raise RuntimeError("no model loaded")
        # lists count as leaves so YAML-sourced examples ({input:
        # [[1,2,3]]}) become proper arrays, not 0-d scalar trees
        example = jax.tree_util.tree_map(
            np.asarray, example_input,
            is_leaf=lambda v: isinstance(v, list))
        done = set()
        # mark these compiles as intentional: warming the whole bucket
        # ladder mints N distinct shapes in seconds, which must not
        # read as a recompile storm. The warming() context is thread-
        # local and reaches EVERY compile boundary the warm trace
        # crosses (this bucket cache AND a graph-backed model's
        # GraphFunction signatures)
        with warming():
            for bs in batch_sizes:
                bucket = self._bucket_for(bs)
                if bucket in done:
                    continue
                done.add(bucket)
                batch = jax.tree_util.tree_map(
                    lambda a: np.repeat(a[:1], bucket, axis=0), example)
                self.predict(batch)
        return self

    # ---------------------------------------------------------- predict --
    def _shape_key(self, x) -> Any:
        return tuple(
            (getattr(l, "shape", None), str(getattr(l, "dtype", "")))
            for l in jax.tree_util.tree_leaves(x))

    def predict(self, x, batch_size: Optional[int] = None) -> Any:
        """Thread-safe batched prediction with shape-bucket AOT cache
        (ref: doPredict, InferenceModel.scala:28-62 -- minus the model
        queue)."""
        out, n = self.predict_async(x)
        return jax.tree_util.tree_map(
            lambda a: np.asarray(a)[:n], out)

    def predict_async(self, x) -> Any:
        """Dispatch prediction WITHOUT materializing results: returns
        (device_outputs, n). jax dispatch is asynchronous, so the
        caller can submit the next batch (overlapping its host->device
        transfer with this batch's compute) before fetching these
        outputs with ``np.asarray(...)[:n]``. The serving worker's
        engine is built on this."""
        if self._apply_fn is None:
            raise RuntimeError("no model loaded")
        # canonicalize 64-bit host inputs (JSON ints/floats) to the
        # 32-bit dtypes jax runs anyway -- otherwise the shape-bucket
        # key differs from warmed buckets and recompiles pointlessly
        def canon(a):
            a = np.asarray(a)
            if a.dtype == np.float64:
                return a.astype(np.float32)
            if a.dtype == np.int64:
                return a.astype(np.int32)
            return a

        x = jax.tree_util.tree_map(canon, x)
        leaves = jax.tree_util.tree_leaves(x)
        n = leaves[0].shape[0]
        plan = self.shard_plan
        bucket = self._bucket_for(n)

        def pad(a):
            if a.shape[0] == bucket:
                return a
            reps = np.concatenate(
                [a, np.repeat(a[-1:], bucket - a.shape[0], axis=0)])
            return reps

        padded = jax.tree_util.tree_map(pad, x)
        # sharding-aware cache key: the plain shape tuple single-chip
        # (EXACTLY the pre-mesh key, so warm persistent caches survive
        # the upgrade) and (shapes, plan signature) under a mesh --
        # single-chip and sharded entries, or two different meshes,
        # can never collide
        key = self._shape_key(padded)
        mode = "off"
        if plan is not None:
            key = (key, plan.signature)
            mode = plan.label
            padded = plan.place_batch(padded)
        with self._lock:
            fn = self._compiled.get(key)
            fresh = fn is None
            if fresh:
                fn = (plan.build_fn(self._apply_fn) if plan is not None
                      else jax.jit(self._apply_fn))
                self._compiled[key] = fn
                _M_COMPILES.labels(bucket=str(bucket), mode=mode).inc()
                logger.info("inference: compiling bucket %s", key)
        _M_DISPATCH.labels(mode=mode).inc()
        _M_PAD.labels(bucket=str(bucket),
                      mode=mode).observe((bucket - n) / bucket)
        if fresh:
            # first dispatch of a new bucket: jax traces + XLA-compiles
            # synchronously inside this call, so its wall time ~= the
            # compile stall requests behind it paid. The event feeds the
            # recompile-storm detector -- a serving deployment whose
            # traffic keeps minting new buckets (bad bucketing, ragged
            # inputs) warns loudly instead of just running slow.
            t0 = time.perf_counter()
            out = fn(self.variables, padded)
            record_compile("inference.predict", key,
                           time.perf_counter() - t0,
                           subsystem="inference")
            return out, n
        return fn(self.variables, padded), n
