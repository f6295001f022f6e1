"""Cluster/runtime context initialization.

The TPU-native analog of the reference's ``NNContext.initNNContext`` +
``init_orca_context`` (ref: zoo/.../common/NNContext.scala:134-150,
pyzoo/zoo/common/nncontext.py:319-392, pyzoo/zoo/orca/common.py:21-218).

Where the reference creates a SparkContext, pins MKL/OMP env, initializes the
BigDL engine, and optionally boots a Ray cluster inside Spark executors
(RayOnSpark), here one call:

- optionally initializes ``jax.distributed`` for multi-host (DCN) runs
  (the analog of the cluster bootstrap in init_spark_on_yarn/k8s),
- discovers local + global devices,
- builds the default device mesh (data-parallel unless told otherwise),
- installs the global config.

There is exactly ONE runtime to initialize -- JAX SPMD -- instead of five
(Spark+BigDL, Ray, Flink, Horovod, MXNet PS); see SURVEY.md section 2.3.
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np

from analytics_zoo_tpu.common.config import ZooConfig, get_config
from analytics_zoo_tpu.common.log import get_logger

logger = get_logger(__name__)

# the one fixed persistent-cache location when the environment names
# none: inside the checkout (git-ignored), derived from the package
# location so every entry point -- tests, benches, chip_smoke.py, the
# launcher's replicas -- lands in the same place from any cwd. The
# directory is part of XLA's cache key, so a path that moved (a temp
# name, a pid, ~ of another user) would never hit.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")

def backend_initialized() -> bool:
    """Whether this process already holds a JAX backend -- WITHOUT
    initializing one. ``jax.devices()`` / ``jax.default_backend()``
    would take the chip; processes meant to stay off the device (a
    fleet controller, a router, a debug endpoint) ask here instead.
    jax has no public spelling of this, hence the one private import."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def enable_compilation_cache() -> None:
    """Turn on XLA's persistent compilation cache so the first-compile
    tax is paid once per machine, not once per process; serving
    restarts and preemption-resumes then start at steady-state speed.

    Placement belongs to the environment: when
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and no
    directory is set in code. Only when it is unset does the cache go
    to :data:`COMPILE_CACHE_DIR`. Idempotent; called automatically by
    ``init_zoo_context``, the Estimator, and ``InferenceModel``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


class ZooContext:
    """Singleton runtime context.

    Attributes:
      config: the layered ZooConfig.
      devices: global (across hosts) jax devices.
      local_devices: devices attached to this host/process.
      mesh: the default ``jax.sharding.Mesh`` (data-parallel over all
        devices unless ``mesh_shape`` was given at init).
    """

    _instance: Optional["ZooContext"] = None
    _lock = threading.Lock()

    # class-level feature flags, the analog of the reference ZooContext
    # metaclass properties (ref: pyzoo/zoo/common/nncontext.py:269-316)
    log_output: bool = True

    def __init__(
        self,
        cluster_mode: str = "local",
        mesh_shape: Optional[Dict[str, int]] = None,
        config: Optional[ZooConfig] = None,
    ):
        self.cluster_mode = cluster_mode
        self.config = config or get_config()
        self.devices = jax.devices()
        self.local_devices = jax.local_devices()
        self.num_processes = jax.process_count()
        self.process_id = jax.process_index()
        self._mesh_shape = mesh_shape
        self.mesh = self._build_mesh(mesh_shape)

    def _build_mesh(self, mesh_shape: Optional[Dict[str, int]]):
        # delegate to the canonical builder: hybrid ICI x DCN layout on
        # multi-host, -1 axis inference, validation.
        from analytics_zoo_tpu.parallel.mesh import create_mesh

        if not mesh_shape:
            axis = self.config.get("zoo.mesh.axis.data")
            return create_mesh({axis: len(self.devices)})
        return create_mesh(mesh_shape)

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    def barrier(self, name: str = "zoo_barrier") -> None:
        """Block until all processes reach this point (no-op single-host)."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(name)

    def stop(self) -> None:
        with ZooContext._lock:
            if ZooContext._instance is not self:
                return  # stale handle; don't tear down a newer context
            ZooContext._instance = None
        if self.cluster_mode == "multihost":
            try:
                jax.distributed.shutdown()
            except RuntimeError:
                pass

    @classmethod
    def get(cls) -> Optional["ZooContext"]:
        with cls._lock:
            return cls._instance


def init_zoo_context(
    cluster_mode: str = "local",
    mesh_shape: Optional[Dict[str, int]] = None,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    conf: Optional[Dict[str, Any]] = None,
) -> ZooContext:
    """Initialize (or fetch) the global runtime context.

    Args:
      cluster_mode: "local" (single host, all local chips) or "multihost"
        (jax.distributed over DCN; the analog of init_spark_on_yarn/k8s,
        ref: pyzoo/zoo/common/nncontext.py:31-244).
      mesh_shape: optional ordered {axis_name: size} for the default mesh,
        e.g. {"data": 8} or {"data": 2, "model": 4}. Defaults to pure
        data parallelism over every visible device.
      coordinator_address / num_processes / process_id: multihost rendezvous
        parameters, forwarded to ``jax.distributed.initialize``.
      conf: extra config overrides, applied to the global ZooConfig
        (the analog of extra spark conf dict).
    """
    if cluster_mode not in ("local", "multihost"):
        raise ValueError(
            f"unknown cluster_mode {cluster_mode!r}; use 'local' or 'multihost'"
        )

    with ZooContext._lock:
        if ZooContext._instance is not None:
            existing = ZooContext._instance
            if (mesh_shape is not None and mesh_shape != existing._mesh_shape) \
                    or cluster_mode != existing.cluster_mode or conf:
                logger.warning(
                    "init_zoo_context called with new arguments but a context "
                    "already exists; returning the existing context "
                    "(mode=%s, mesh=%s). Call stop_orca_context() first to "
                    "re-initialize.", existing.cluster_mode,
                    dict(zip(existing.mesh.axis_names,
                             existing.mesh.devices.shape)))
            return existing

        dist_started_here = False
        if cluster_mode == "multihost":
            kwargs: Dict[str, Any] = {}
            if coordinator_address is not None:
                kwargs["coordinator_address"] = coordinator_address
            if num_processes is not None:
                kwargs["num_processes"] = num_processes
            if process_id is not None:
                kwargs["process_id"] = process_id
            # a previous init attempt may have failed *after* this point;
            # reuse the live distributed runtime rather than poisoning every
            # future init (jax raises on double-initialize).
            if not jax.distributed.is_initialized():
                jax.distributed.initialize(**kwargs)
                dist_started_here = True

        config = get_config()
        if conf:
            for k, v in conf.items():
                config.set(k, v)
        enable_compilation_cache()

        try:
            ctx = ZooContext(cluster_mode=cluster_mode, mesh_shape=mesh_shape,
                             config=config)
        except Exception:
            if dist_started_here:
                try:
                    jax.distributed.shutdown()
                except RuntimeError:
                    pass
            raise
        ZooContext._instance = ctx
    logger.info(
        "initialized ZooContext: mode=%s processes=%d devices=%d mesh=%s",
        cluster_mode, ctx.num_processes, ctx.num_devices,
        dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape)),
    )
    return ctx


# Orca-compatible aliases (ref: pyzoo/zoo/orca/common.py init_orca_context /
# stop_orca_context): one unified entry point for users of the reference API.
def init_orca_context(cluster_mode: str = "local", **kwargs) -> ZooContext:
    return init_zoo_context(cluster_mode=cluster_mode, **kwargs)


def stop_orca_context() -> None:
    ctx = ZooContext.get()
    if ctx is not None:
        ctx.stop()


atexit.register(stop_orca_context)
