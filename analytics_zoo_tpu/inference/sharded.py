"""Mesh-routed serving: the sharding layer behind ``InferenceModel``.

PRs 1-6 made single-chip serving fast, observable and crash-safe;
``parallel/`` ships exact tensor-parallel recipes and the MULTICHIP
dryrun proves out an 8-device mesh -- but every prediction still ran on
one chip. This module routes ``predict_async`` through a
``jax.sharding.Mesh`` per deployment config (the ROADMAP "sharded
multi-chip inference" item; mesh-native TPU serving per the Gemma-on-TPU
study, arXiv:2605.25645):

``zoo.serving.shard.mode``
    - ``off``   (default) -- single-chip, byte-identical to the pre-mesh
      engine, including the exact compile-cache keys (warm persistent
      XLA caches survive the upgrade);
    - ``tp``    -- tensor parallel: parameters sharded over the
      ``zoo.mesh.axis.model`` axis by a ``parallel.recipes`` spec
      (``zoo.serving.shard.recipe``), batch replicated; GSPMD inserts
      the exact collectives (megatron row/column layout). The big-model
      mode: 1/N parameter HBM per chip and N chips on every matmul.
    - ``dp``    -- data parallel: parameters replicated, batch sharded
      over the ``zoo.mesh.axis.data`` axis. The small-model mode: N
      independent replicas behind one dispatch.
    - ``auto``  -- picks ``tp`` when the parameter bytes exceed
      ``zoo.serving.shard.auto_hbm_fraction`` of one chip's HBM
      (``memory_stats()``, overridable via
      ``zoo.serving.shard.auto_hbm_bytes``), else ``dp``.

``zoo.serving.shard.quantized_collectives``
    Opt-in EQuARX-idiom wire compression (arXiv:2506.17615) for the
    ``tp`` mode: parameters stay resident as shards (same 1/N HBM at
    rest) and the engine executes a ``shard_map`` whose body re-assembles
    the tensor-parallel shards through an **int8 all-gather with
    per-shard rescale** (:func:`parallel.collectives.quantized_all_gather`
    -- ~1/4 the cross-chip bytes of f32) and computes each chip's slice
    of the batch locally. Approximate (documented tolerance: the int8
    round-trip bounds relative error at ~1/127 per shard); the exact
    GSPMD path stays the default.

The compile-cache consequence, handled in ``inference_model.py``: a
plan contributes a ``signature`` (mode, axis, recipe, device set) to
the bucket cache key, so single-chip and sharded entries -- or two
different meshes -- can never collide; with ``mode=off`` the key is
exactly the pre-mesh tuple.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.common.log import get_logger
from analytics_zoo_tpu.obs.metrics import get_registry
from analytics_zoo_tpu.parallel.mesh import shard_map

logger = get_logger(__name__)

# per-mesh serving visibility (obs): how many chips the active plan
# spans, by mode -- the companion of the bucket/mode labels on the
# zoo_inference_* compile/dispatch series
_M_MESH = get_registry().gauge(
    "zoo_inference_mesh_devices_items",
    "Devices spanned by the active serving shard plan, by mode",
    labelnames=("mode",))
_MESH_LABELS = ("tp", "dp", "tp_q8")


def _set_mesh_gauge(active_label: Optional[str], n: int) -> None:
    """One active mesh at a time: setting a mode zeroes the others, so
    a process that resolved several plans (benches, re-launches, a
    mode=off restart) never scrapes as running multiple meshes."""
    for label in _MESH_LABELS:
        _M_MESH.labels(mode=label).set(
            n if label == active_label else 0)

_MODES = ("off", "tp", "dp", "auto")
_RECIPES = ("transformer_tp", "embedding_tp")
# what "auto" plans against on the CPU backend, which reports no
# memory_stats (the virtual-device test meshes): one v5e chip
_CPU_MESH_HBM_BYTES = 16 << 30


def _spec_fn_for(recipe: str, axis: str) -> Callable:
    from analytics_zoo_tpu.parallel import recipes

    if recipe == "embedding_tp":
        return recipes.embedding_tp_spec(axis=axis)
    return recipes.transformer_tp_spec(axis=axis)


def _sharded_dim(spec: P, axis: str) -> Optional[int]:
    """Index of the dimension ``spec`` shards over ``axis`` (None when
    the spec never mentions it; tuple entries count)."""
    for i, entry in enumerate(spec):
        if entry == axis or (isinstance(entry, (tuple, list))
                             and axis in entry):
            return i
    return None


def _leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _param_bytes(variables: Any) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(variables):
        size = int(np.prod(getattr(leaf, "shape", ()) or (1,)))
        itemsize = np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
        total += size * itemsize
    return total


def _per_chip_bytes(device, cfg_get=None) -> int:
    if cfg_get is None:
        cfg_get = get_config().get
    override = int(cfg_get("zoo.serving.shard.auto_hbm_bytes", 0))
    if override:
        return override
    if device.platform == "cpu":
        return _CPU_MESH_HBM_BYTES
    stats = device.memory_stats()
    if not stats or not stats.get("bytes_limit"):
        raise RuntimeError(
            f"shard auto: {device} reports no memory_stats bytes_limit; "
            "set zoo.serving.shard.auto_hbm_bytes to plan without it")
    return int(stats["bytes_limit"])


class ShardPlan:
    """A resolved serving sharding decision: the mesh, the per-leaf
    parameter specs, how batches place, and the cache-key signature.
    Built by :func:`resolve_shard_plan`; attached to an
    ``InferenceModel`` via ``model.shard(plan)``."""

    def __init__(self, mode: str, mesh: Mesh, axis: str,
                 recipe: Optional[str], quantized: bool,
                 spec_fn: Optional[Callable]):
        self.mode = mode                  # "tp" | "dp" (resolved)
        self.mesh = mesh
        self.axis = axis
        self.recipe = recipe              # None for dp
        self.quantized = quantized and mode == "tp"
        self.spec_fn = spec_fn            # None for dp (replicate)
        self.n_devices = int(np.prod(mesh.devices.shape))
        # batch constraint: modes that split the batch across the mesh
        # need device batches divisible by the axis size; exact tp
        # replicates the batch, so any bucket works
        self.batch_multiple = (self.n_devices
                               if mode == "dp" or self.quantized else 1)
        device_ids = tuple(int(d.id) for d in mesh.devices.flat)
        self.label = mode + ("_q8" if self.quantized else "")
        # the compile-cache key contribution: device set + mode/spec
        # signature, so single-chip and sharded entries (or two
        # different meshes/recipes) never collide
        self.signature: Tuple = ("shard", self.label, axis,
                                 recipe or "", device_ids)
        self._spec_tree = None  # per-leaf P tree, built at placement

    # ------------------------------------------------------ placement --
    def place_variables(self, variables: Any) -> Any:
        """Commit the parameter pytree onto the mesh (sharded per the
        recipe spec for tp, replicated for dp) and remember the spec
        tree the quantized engine's ``shard_map`` needs."""
        if self.spec_fn is None:
            self._spec_tree = jax.tree_util.tree_map(
                lambda _: P(), variables)
        else:
            self._spec_tree = jax.tree_util.tree_map_with_path(
                lambda p, leaf: self.spec_fn(p, leaf), variables)
        shardings = jax.tree_util.tree_map(
            lambda spec: NamedSharding(self.mesh, spec),
            self._spec_tree)
        # placement IS activation (InferenceModel.shard commits here,
        # exactly once per model): the mesh gauge flips to this plan
        # and zeroes whatever mode a previous plan advertised
        _set_mesh_gauge(self.label, self.n_devices)
        return jax.tree_util.tree_map(jax.device_put, variables,
                                      shardings)

    def batch_spec(self) -> P:
        """Input placement: batch-sharded over the mesh axis for the
        batch-splitting modes, replicated for exact tp."""
        return P(self.axis) if self.batch_multiple > 1 else P()

    def place_batch(self, padded: Any) -> Any:
        sharding = NamedSharding(self.mesh, self.batch_spec())
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sharding), padded)

    # ---------------------------------------------------- compilation --
    def build_fn(self, apply_fn: Callable) -> Callable:
        """The callable the bucket cache compiles for this plan: plain
        jit for the exact modes (GSPMD reads the committed shardings),
        or the quantized-gather ``shard_map`` engine."""
        if not self.quantized:
            return jax.jit(apply_fn)
        if self._spec_tree is None:
            raise RuntimeError("place_variables must run before "
                               "build_fn on a quantized plan")
        from analytics_zoo_tpu.parallel.collectives import (
            quantized_all_gather)

        axis = self.axis
        spec_leaves = self._spec_tree

        def body(local_vars, x_local):
            # re-assemble each tensor-parallel shard through the int8
            # gather; replicated leaves (LayerNorms, biases of
            # row-parallel layers) pass through untouched
            def gather(leaf, spec):
                dim = _sharded_dim(spec, axis)
                if dim is None:
                    return leaf
                return quantized_all_gather(leaf, axis, axis=dim)

            full = jax.tree_util.tree_map(gather, local_vars,
                                          spec_leaves)
            return apply_fn(full, x_local)

        fn = shard_map(body, self.mesh,
                       (self._spec_tree, self.batch_spec()),
                       self.batch_spec())
        return jax.jit(fn)

    # -------------------------------------------------------- surface --
    def describe(self) -> Dict[str, Any]:
        """The protocol-visible shard info (/debug/vars ``serving_shard``
        block, ``worker.metrics()['shard']``)."""
        return {
            "mode": self.mode,
            "quantized_collectives": self.quantized,
            "axis": self.axis,
            "recipe": self.recipe,
            "devices": self.n_devices,
            "platform": self.mesh.devices.flat[0].platform,
            "batch_multiple": self.batch_multiple,
        }


def _validate_tp(variables: Any, spec_fn: Callable, axis: str,
                 n: int) -> List[str]:
    """Names of leaves the recipe shards; raises when a sharded dim
    does not divide by the axis size (a clear error beats jax's)."""
    sharded: List[str] = []
    bad: List[str] = []
    flat = jax.tree_util.tree_flatten_with_path(variables)[0]
    for path, leaf in flat:
        spec = spec_fn(path, leaf)
        dim = _sharded_dim(spec, axis)
        if dim is None:
            continue
        name = _leaf_name(path)
        sharded.append(name)
        shape = getattr(leaf, "shape", ())
        if dim >= len(shape) or shape[dim] % n:
            bad.append(f"{name}{tuple(shape)} dim {dim}")
    if bad:
        raise ValueError(
            f"zoo.serving.shard.mode=tp cannot shard over {n} devices: "
            f"{', '.join(bad[:4])} not divisible by the axis size "
            "(pick a smaller zoo.serving.shard.devices or mode=dp)")
    return sharded


def resolve_shard_plan(variables: Any, devices=None,
                       overrides: Optional[Dict[str, Any]] = None
                       ) -> Optional[ShardPlan]:
    """Read ``zoo.serving.shard.*`` and build the deployment's plan
    (None = mode off / nothing to shard over). ``auto`` resolves by
    parameter bytes vs per-chip HBM; an ``auto`` tp whose recipe cannot
    shard this parameter tree falls back to dp instead of failing the
    launch. ``overrides`` (full ``zoo.serving.shard.*`` key names) win
    over the config layer for THIS resolution only -- the launcher's
    YAML ``shard:`` block rides here instead of mutating the
    process-global config, so a later launch in the same process
    cannot inherit a previous deployment's sharding."""
    cfg = get_config()
    over = overrides or {}

    def _cfg(key, default):
        return over[key] if key in over else cfg.get(key, default)

    mode = str(_cfg("zoo.serving.shard.mode", "off"))
    if mode not in _MODES:
        raise ValueError(f"zoo.serving.shard.mode must be one of "
                         f"{_MODES}, got {mode!r}")
    if mode == "off":
        return None
    devices = list(devices) if devices is not None else jax.devices()
    limit = int(_cfg("zoo.serving.shard.devices", 0))
    if limit:
        devices = devices[:limit]
    if len(devices) < 2:
        logger.warning("shard.mode=%s requested but only %d device(s) "
                       "available; serving single-chip", mode,
                       len(devices))
        return None
    quantized = bool(_cfg(
        "zoo.serving.shard.quantized_collectives", False))
    recipe = str(_cfg("zoo.serving.shard.recipe", "transformer_tp"))
    if recipe not in _RECIPES:
        raise ValueError(f"zoo.serving.shard.recipe must be one of "
                         f"{_RECIPES}, got {recipe!r}")
    auto = mode == "auto"
    if auto:
        pbytes = _param_bytes(variables)
        budget = (float(_cfg("zoo.serving.shard.auto_hbm_fraction",
                             0.6))
                  * _per_chip_bytes(devices[0], _cfg))
        mode = "tp" if pbytes > budget else "dp"
        logger.info("shard.mode=auto: %d param bytes vs %.0f per-chip "
                    "budget -> %s", pbytes, budget, mode)

    from analytics_zoo_tpu.parallel.mesh import config_axis, create_mesh

    if mode == "tp":
        axis = config_axis("model")
        spec_fn = _spec_fn_for(recipe, axis)
        try:
            sharded = _validate_tp(variables, spec_fn, axis,
                                   len(devices))
        except ValueError:
            if not auto:
                raise
            sharded = []
        if not sharded:
            if auto:
                logger.info("shard.mode=auto: recipe %r shards nothing "
                            "on this tree; falling back to dp", recipe)
                mode = "tp_fallback_dp"
            else:
                logger.warning(
                    "shard.mode=tp: recipe %r shards NO parameter of "
                    "this model (suffixes never matched); serving will "
                    "replicate the full tree on every chip", recipe)
        if mode == "tp":
            mesh = create_mesh({axis: len(devices)}, devices=devices)
            plan = ShardPlan("tp", mesh, axis, recipe, quantized,
                             spec_fn)
            return plan
    axis = config_axis("data")
    if quantized:
        # dp has no cross-chip reduction on the predict path -- nothing
        # for the quantized collective to compress
        logger.info("shard.quantized_collectives is a no-op under dp "
                    "(no cross-chip reduction on the predict path)")
    mesh = create_mesh({axis: len(devices)}, devices=devices)
    plan = ShardPlan("dp", mesh, axis, None, False, None)
    return plan


def maybe_shard_from_config(model, devices=None, overrides=None):
    """Launcher hook: resolve the deployment's plan (config layer +
    per-launch ``overrides``) and attach it to the model. A deployment
    that resolves to single-chip (mode off, degraded device count)
    zeroes the mesh gauge -- a relaunch must not keep advertising a
    previous deployment's mesh. Returns the plan (or None)."""
    plan = resolve_shard_plan(model.variables, devices=devices,
                              overrides=overrides)
    if plan is not None:
        model.shard(plan)
        from analytics_zoo_tpu.obs.events import emit as emit_event

        emit_event("shard_attached", "serving", **plan.describe())
        logger.info("serving sharded: %s", plan.describe())
    else:
        _set_mesh_gauge(None, 0)
    return plan
