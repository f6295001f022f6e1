"""ZooDataset: the training-facing sharded dataset.

Analog of ``TFDataset`` (ref: pyzoo/zoo/tfpark/tf_dataset.py:115-1279) +
``FeatureSet`` memory tiers (ref: zoo/.../feature/FeatureSet.scala:644-683).

Contracts carried over from the reference:
- global batch size must divide evenly over the parallel workers
  (ref: tf_dataset.py:142-147 enforces ``batch_size % total_cores == 0``);
  here: over the mesh's data-axis size, checked in :meth:`batches`.
- datasets can be cached in DRAM or spilled to disk
  (``memory_type="DRAM" | "DISK"``; the reference's PMEM tier serves the
  same larger-than-RAM role, ref: FeatureSet.scala memoryType).
- deterministic epoch shuffling with a seed, sequential order optional
  (ref: FeatureSet ``sequentialOrder``/``shuffle`` flags).

Yields *host-local* numpy batches; ``device_iterator`` additionally places
them on the mesh (sharded along the data axis) with one-batch lookahead so
host->HBM transfer overlaps the train step.
"""

from __future__ import annotations

import os
import queue
import tempfile
import threading
import time
from typing import Any, Callable, Iterator, Optional, Tuple

import jax
import numpy as np

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.common.log import get_logger
from analytics_zoo_tpu.obs.tracing import get_tracer

logger = get_logger(__name__)


def _tree_map(fn, tree):
    return jax.tree_util.tree_map(fn, tree)


def _leading_dim(tree) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        raise ValueError("empty pytree")
    n = leaves[0].shape[0]
    for l in leaves:
        if l.shape[0] != n:
            raise ValueError("all arrays must share the leading dim")
    return n


def _take_chunked(tree, idx, memory_type: str, cache_dir: str,
                  chunk: int = 65536):
    """Index-select rows from a pytree; DISK tier streams through a new
    memmap in chunks so selection never materializes fully in RAM."""
    if memory_type != "DISK":
        return _tree_map(lambda a: np.asarray(a)[idx], tree)
    os.makedirs(cache_dir, exist_ok=True)
    counter = [0]

    def take(a):
        path = os.path.join(cache_dir, f"arr_{counter[0]}.npy")
        counter[0] += 1
        out = np.lib.format.open_memmap(
            path, mode="w+", dtype=a.dtype, shape=(len(idx),) + a.shape[1:])
        for s in range(0, len(idx), chunk):
            sel = idx[s:s + chunk]
            out[s:s + len(sel)] = a[sel]
        out.flush()
        return np.load(path, mmap_mode="r")

    return _tree_map(take, tree)


def _spill_to_disk(tree, cache_dir: str):
    """Replace each array with a read-only memmap backed by ``cache_dir``."""
    os.makedirs(cache_dir, exist_ok=True)
    counter = [0]

    def spill(x):
        x = np.asarray(x)
        path = os.path.join(cache_dir, f"arr_{counter[0]}.npy")
        counter[0] += 1
        np.save(path, x)
        return np.load(path, mmap_mode="r")

    return _tree_map(spill, tree)


class ZooDataset:
    """An in-memory (or disk-tiered) dataset of features + optional labels.

    ``features`` / ``labels`` are pytrees (array, dict, or tuple of arrays)
    sharing a leading sample dimension.
    """

    def __init__(self, features: Any, labels: Any = None,
                 memory_type: str = "DRAM",
                 cache_dir: Optional[str] = None):
        memory_type = memory_type.upper()
        if memory_type not in ("DRAM", "DISK"):
            raise ValueError(
                f"memory_type must be DRAM or DISK, got {memory_type!r}")
        features = _tree_map(np.asarray, features)
        labels = _tree_map(np.asarray, labels) if labels is not None else None
        self._n = _leading_dim(features)
        if labels is not None and _leading_dim(labels) != self._n:
            raise ValueError("features and labels disagree on sample count")
        if memory_type == "DISK":
            owned = cache_dir is None
            cache_dir = cache_dir or tempfile.mkdtemp(prefix="zoo_dataset_")
            features = _spill_to_disk(features, os.path.join(cache_dir, "x"))
            if labels is not None:
                labels = _spill_to_disk(labels, os.path.join(cache_dir, "y"))
            logger.info("dataset spilled to disk tier at %s", cache_dir)
            if owned:
                self._own_cache_dir(cache_dir)
        self.features = features
        self.labels = labels
        self.memory_type = memory_type

    def _own_cache_dir(self, cache_dir: str) -> None:
        """Delete a framework-created spill dir when the dataset is GC'd
        (user-supplied cache_dirs are never touched)."""
        import shutil
        import weakref

        weakref.finalize(self, shutil.rmtree, cache_dir,
                         ignore_errors=True)

    # ----------------------------------------------------- constructors --
    @staticmethod
    def from_ndarrays(features: Any, labels: Any = None,
                      **kwargs) -> "ZooDataset":
        """Mirror of ``TFDataset.from_ndarrays`` (ref: tf_dataset.py:322)."""
        return ZooDataset(features, labels, **kwargs)

    @staticmethod
    def from_xshards(shards, feature_cols=None, label_cols=None,
                     **kwargs) -> "ZooDataset":
        """Build from an XShards of dicts / DataFrames
        (ref: orca Estimator fit accepting SparkXShards)."""
        import pandas as pd

        merged = shards.merged()
        if isinstance(merged, pd.DataFrame):
            if feature_cols is None:
                raise ValueError("feature_cols required for DataFrame shards")
            feats = {c: merged[c].to_numpy() for c in feature_cols}
            labels = ({c: merged[c].to_numpy() for c in label_cols}
                      if label_cols else None)
            if labels is not None and len(labels) == 1:
                labels = next(iter(labels.values()))
            return ZooDataset(feats, labels, **kwargs)
        if isinstance(merged, dict):
            if feature_cols is None and "x" in merged:
                feats = merged["x"]
                labels = merged.get("y")
            else:
                feature_cols = feature_cols or list(merged.keys())
                feats = {c: merged[c] for c in feature_cols}
                labels = ({c: merged[c] for c in label_cols}
                          if label_cols else None)
                if labels is not None and len(labels) == 1:
                    labels = next(iter(labels.values()))
            return ZooDataset(feats, labels, **kwargs)
        return ZooDataset(merged, **kwargs)

    # ----------------------------------------------------------- queries --
    @property
    def num_samples(self) -> int:
        return self._n

    def __len__(self) -> int:
        return self._n

    def split(self, fraction: float, seed: int = 0
              ) -> Tuple["ZooDataset", "ZooDataset"]:
        """Random split into (first, second) with ``fraction`` in first.
        Children inherit the memory tier; DISK-tier data is copied in
        chunks so a larger-than-RAM dataset never fully materializes."""
        rng = np.random.RandomState(seed)
        perm = rng.permutation(self._n)
        cut = int(self._n * fraction)
        first, second = perm[:cut], perm[cut:]

        def make(idx):
            cache_dir = (tempfile.mkdtemp(prefix="zoo_split_")
                         if self.memory_type == "DISK" else "")
            # distinct subdirs: _take_chunked restarts its arr_<n> counter
            # per call, so sharing one dir would overwrite features with
            # labels
            feats = _take_chunked(self.features, idx, self.memory_type,
                                  os.path.join(cache_dir, "x"))
            labs = (_take_chunked(self.labels, idx, self.memory_type,
                                  os.path.join(cache_dir, "y"))
                    if self.labels is not None else None)
            # _take_chunked already produced disk-backed memmaps for the
            # DISK tier; construct as DRAM to avoid a second spill copy,
            # then restore the tier label.
            child = ZooDataset(feats, labs)
            child.memory_type = self.memory_type
            if cache_dir:
                child._own_cache_dir(cache_dir)
            return child

        return make(first), make(second)

    def map_features(self, fn: Callable) -> "ZooDataset":
        return ZooDataset(fn(self.features), self.labels)

    # --------------------------------------------------------- iteration --
    def steps_per_epoch(self, batch_size: int,
                        drop_remainder: bool = True) -> int:
        if drop_remainder:
            return self._n // batch_size
        return -(-self._n // batch_size)

    def batches(self, batch_size: int, shuffle: bool = True, seed: int = 0,
                epoch: int = 0, drop_remainder: bool = True,
                mesh=None, with_mask: bool = False
                ) -> Iterator[Tuple[Any, ...]]:
        """Yield host-local numpy ``(features, labels)`` batches.

        ``batch_size`` is the GLOBAL batch size; it must divide by the
        mesh's data-axis size (ref contract: tf_dataset.py:142-147). On a
        multi-process run, each process yields its 1/num_processes slice of
        every global batch (samples strided by process index).

        With ``drop_remainder=False`` the final short batch is padded up to
        ``batch_size`` by wrapping (tiling) the epoch's samples, keeping
        every batch shape static for XLA and divisible for sharding
        (predict paths truncate outputs back to ``num_samples``). With
        ``with_mask=True`` each yield is ``(x, y, mask)`` where ``mask``
        is a local float32 [local_bs] vector with 0 marking padded rows --
        used by evaluate for exact tail-inclusive metrics.
        """
        n_data = 1
        if mesh is not None:
            from analytics_zoo_tpu.parallel.mesh import mesh_axis_size

            n_data = mesh_axis_size(mesh, "data")
        if batch_size % max(n_data, 1) != 0:
            # opt-out knob (zoo.data.check_batch_divisible) for callers
            # that shard manually; with the check off, XLA raises later
            # at placement instead of here with a readable message
            if get_config().get("zoo.data.check_batch_divisible", True):
                raise ValueError(
                    f"global batch_size {batch_size} must be divisible "
                    f"by the data-parallel degree {n_data} "
                    "(ref contract: tf_dataset.py:142-147)")
            logger.warning(
                "batch_size %d is not divisible by the data-parallel "
                "degree %d (zoo.data.check_batch_divisible is off)",
                batch_size, n_data)

        n_proc = jax.process_count()
        proc = jax.process_index()
        if batch_size % n_proc != 0:
            raise ValueError(
                f"global batch_size {batch_size} must divide over "
                f"{n_proc} processes")
        local_bs = batch_size // n_proc

        if shuffle:
            rng = np.random.RandomState((seed * 100003 + epoch) & 0x7FFFFFFF)
            order = rng.permutation(self._n)
        else:
            order = np.arange(self._n)

        n_batches = self.steps_per_epoch(batch_size, drop_remainder)
        for b in range(n_batches):
            global_idx = order[b * batch_size:(b + 1) * batch_size]
            n_valid = len(global_idx)
            if n_valid < batch_size:  # pad final short batch (tiled wrap)
                pad = np.resize(order, batch_size - n_valid)
                global_idx = np.concatenate([global_idx, pad])
            # contiguous per-process block: process p owns global rows
            # [p*local_bs, (p+1)*local_bs) -- matches the device order of
            # hybrid meshes (DCN outermost), so the assembled global array
            # preserves batch order (unlike strided slicing)
            local_positions = np.arange(proc * local_bs,
                                        (proc + 1) * local_bs)
            local_idx = global_idx[local_positions]
            x = _tree_map(lambda a: np.asarray(a[local_idx]), self.features)
            y = (_tree_map(lambda a: np.asarray(a[local_idx]), self.labels)
                 if self.labels is not None else None)
            if with_mask:
                mask = (local_positions < n_valid).astype(np.float32)
                yield x, y, mask
            else:
                yield x, y

    def device_iterator(self, batch_size: int, mesh=None, shuffle: bool = True,
                        seed: int = 0, epoch: int = 0,
                        drop_remainder: bool = True, with_mask: bool = False,
                        prefetch: Optional[int] = None,
                        spans: Optional[Tuple[str, int]] = None
                        ) -> Iterator[Tuple[Any, ...]]:
        """``batches`` + mesh placement + background prefetch.

        A producer thread stages the next ``prefetch`` device batches
        (default: the ``zoo.data.prefetch_buffer`` config key) while
        the consumer runs the train step -- the analog of FeatureSet's
        cached-RDD prefetch, but across the host->HBM boundary.

        ``spans`` = ``(trace_id, index of the first batch)``: the
        producer records, per batch, a span ``host_batch`` (one
        ``next()`` of ``batches``: slicing, shuffling, stacking on the
        host) and a span ``shard_batch`` (the placement of its parts),
        each with the batch's index ``i``, in the process's span ring
        (``Estimator.fit`` passes its call's id and step index, so a
        step's wait joins the batch that caused it by ``i``).
        """
        if prefetch is None:
            prefetch = int(get_config().get("zoo.data.prefetch_buffer",
                                            2))
        from analytics_zoo_tpu.parallel.mesh import default_mesh
        from analytics_zoo_tpu.parallel.sharding import shard_batch

        mesh = mesh or default_mesh()
        q: "queue.Queue" = queue.Queue(maxsize=prefetch)
        _SENTINEL = object()
        err: list = []
        stop = threading.Event()

        def put(item) -> bool:
            # bounded put that gives up if the consumer went away, so an
            # abandoned iterator never leaks a blocked thread pinning
            # device batches in HBM
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def produce():
            try:
                host = self.batches(batch_size, shuffle, seed, epoch,
                                    drop_remainder, mesh,
                                    with_mask=with_mask)
                if spans is not None:
                    tracer = get_tracer()
                    trace_id, i = spans
                while True:
                    t0 = time.perf_counter()
                    item = next(host, _SENTINEL)
                    if item is _SENTINEL:
                        return
                    t1 = time.perf_counter()
                    placed = tuple(
                        shard_batch(part, mesh) if part is not None else None
                        for part in item)
                    if spans is not None:
                        tracer.add_span("host_batch", trace_id, t0, t1,
                                        cat="train", i=i)
                        tracer.add_span("shard_batch", trace_id, t1,
                                        time.perf_counter(), cat="train",
                                        i=i)
                        i += 1
                    if not put(placed):
                        return
            except BaseException as e:  # surface in consumer
                err.append(e)
            finally:
                put(_SENTINEL)

        t = threading.Thread(target=produce, daemon=True,
                             name="zoo-input-producer")
        t.start()
        try:
            while True:
                item = q.get()
                if item is _SENTINEL:
                    if err:
                        raise err[0]
                    return
                yield item
        finally:
            stop.set()
