"""Estimator: distributed fit / evaluate / predict.

The single training engine replacing the reference's whole L4
(SURVEY.md section 1): ``InternalDistriOptimizer`` (BigDL two-Spark-jobs-
per-iteration allreduce, ref: zoo/.../keras/models/Topology.scala:1145-1548),
the zoo ``Estimator`` facade (ref: zoo/.../pipeline/estimator/Estimator.scala:37-230),
and the per-framework Ray runners (ref: pyzoo/zoo/orca/learn/*).

Where the reference runs "model forward-backward" as Spark job 1 and
"parameter synchronization" as Spark job 2 every iteration, here one jitted
SPMD step does both: the batch is sharded over the mesh's data axis, the
loss is the global-batch mean, and XLA inserts the gradient allreduce
(psum over ICI/DCN) during compilation. The retry-from-checkpoint loop
mirrors InternalDistriOptimizer.train (ref: Topology.scala:1255-1332).
"""

from __future__ import annotations

import inspect
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.common.log import get_logger
from analytics_zoo_tpu.common.triggers import (
    EveryEpoch, Trigger, TriggerState)
from analytics_zoo_tpu.data.dataset import ZooDataset
from analytics_zoo_tpu.learn import checkpoint as ckpt_lib
from analytics_zoo_tpu.learn.metrics import Metric, resolve_metric
from analytics_zoo_tpu.learn.objectives import resolve_loss
from analytics_zoo_tpu.learn.optim import resolve_optimizer
from analytics_zoo_tpu.obs.events import emit, instrument_compiles
from analytics_zoo_tpu.obs.metrics import get_registry
from analytics_zoo_tpu.obs.tracing import get_tracer, new_trace_id
from analytics_zoo_tpu.parallel import sharding
from analytics_zoo_tpu.parallel.mesh import default_mesh, traced_under
from analytics_zoo_tpu.parallel.sharding import replicated

logger = get_logger(__name__)

# training progress in the unified registry (the BigDL ``Metrics``
# counter role): scraping /metrics on a co-located serving frontend --
# or reading Reporter rollups -- shows training and serving side by side
_REG = get_registry()
_M_STEPS = _REG.counter(
    "zoo_learn_steps_total", "Optimization steps completed")
_M_EPOCHS = _REG.counter(
    "zoo_learn_epochs_total", "Training epochs completed")


def training_prng_key(seed: int):
    """PRNG key for the training stream (dropout masks, on-device epoch
    shuffles): the hardware RBG generator on the TPU, where threefry2x32
    dropout mask generation costs ~23 ms/step on BERT-base (b32, L384,
    v5e) and RBG is near-free; elsewhere the default threefry stream,
    so CPU runs stay bit-reproducible across jax versions. XLA does not
    partition RBG's generator: under a data mesh each device draws its
    own rows' bits (``ops.dropout``)."""
    if jax.devices()[0].platform == "tpu":
        return jax.random.key(seed, impl="rbg")
    return jax.random.PRNGKey(seed)


def _as_dataset(data, labeled: bool = True) -> ZooDataset:
    """Coerce to ZooDataset. ``labeled=True`` splits a 2-tuple into
    (features, labels); predict paths pass ``labeled=False`` so a tuple is
    a multi-input feature pytree."""
    if isinstance(data, ZooDataset):
        return data
    from analytics_zoo_tpu.data.shard import XShards

    if isinstance(data, XShards):
        return ZooDataset.from_xshards(data)
    if labeled and isinstance(data, tuple) and len(data) == 2:
        return ZooDataset.from_ndarrays(data[0], data[1])
    return ZooDataset.from_ndarrays(data)


def _call_args(x) -> tuple:
    """Feature pytree -> positional args for the model (tuple splats)."""
    if isinstance(x, tuple):
        return x
    return (x,)


class _FitCall:
    """What the spans of one ``fit`` call share: the collector, the
    call's ``trace_id``, its start, the profiler where ``profile=True``
    asked for one, and ``i``, the index in the call of the step that is
    next (it runs on across the call's epochs)."""

    __slots__ = ("tracer", "trace_id", "t0", "profiler", "i", "_prepared")

    def __init__(self):
        self.t0 = time.perf_counter()
        self.tracer = get_tracer()
        self.trace_id = new_trace_id()
        self.profiler = None
        self.i = 0
        self._prepared = False

    def span(self, name: str, t0: float, t1: float, **args) -> None:
        self.tracer.add_span(name, self.trace_id, t0, t1, cat="train",
                             **args)

    def prepared(self) -> None:
        """Everything before the call's first ``data_wait`` is
        ``fit_prepare``; called where each epoch's loop is about to
        start, it records the span the first time."""
        if not self._prepared:
            self._prepared = True
            self.span("fit_prepare", self.t0, time.perf_counter())


# the stages ``fit(profile=True)`` sums (``TrainingProfiler.summary``)
_PROFILED_STAGES = ("data_wait", "train_step")


class _stage:
    """One timed region of a ``fit`` call (a context manager). The clock
    is read once at entry and once at exit; that one pair feeds the span
    ring, always, and the call's ``TrainingProfiler`` where there is
    one."""

    __slots__ = ("call", "name", "args", "t0", "t1")

    def __init__(self, call: _FitCall, name: str, **args):
        self.call, self.name, self.args = call, name, args

    def __enter__(self) -> "_stage":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        call = self.call
        call.span(self.name, self.t0, self.t1, **self.args)
        if call.profiler is not None and self.name in _PROFILED_STAGES:
            call.profiler.record(self.name, self.t1 - self.t0)


# sow-style collections: written fresh per apply, never carried as
# state (persisting them would grow the tuples every step)
_SOW_COLLECTIONS = ("losses", "intermediates")


class FlaxModelAdapter:
    """Adapts a flax ``nn.Module`` (or compatible object) to the uniform
    (init, apply) the Estimator drives. Detects a ``train``/``deterministic``
    flag on ``__call__`` and non-param variable collections (batch_stats).

    Sow collections (``losses``/``intermediates``) are stripped from the
    stored variables and requested mutable on every training apply, so
    modules that ``sow`` auxiliary losses (e.g. the MoE load-balance
    loss) surface them per step without accumulating state."""

    def __init__(self, module):
        self.module = module
        try:
            sig = inspect.signature(type(module).__call__)
            params = set(sig.parameters)
        except (TypeError, ValueError):
            params = set()
        self._train_kw = ("train" if "train" in params else
                          "deterministic" if "deterministic" in params
                          else None)

    def _mode_kwargs(self, training: bool) -> Dict[str, Any]:
        if self._train_kw == "train":
            return {"train": training}
        if self._train_kw == "deterministic":
            return {"deterministic": not training}
        return {}

    def init(self, rng, x) -> Dict[str, Any]:
        variables = self.module.init({"params": rng, "dropout": rng},
                                     *_call_args(x),
                                     **self._mode_kwargs(False))
        return {k: v for k, v in variables.items()
                if k not in _SOW_COLLECTIONS}

    def apply(self, variables, x, training: bool, rng=None,
              want_sown: bool = False):
        """Returns (preds, new_extra_collections). ``want_sown``
        surfaces the sow collections on an inference apply too (how
        evaluate() folds MoE aux losses into val_loss)."""
        variables = {k: v for k, v in variables.items()
                     if k not in _SOW_COLLECTIONS}
        mutable = [k for k in variables if k != "params"]
        kwargs = self._mode_kwargs(training)
        rngs = {"dropout": rng} if (training and rng is not None) else None
        if training or want_sown:
            preds, new_extra = self.module.apply(
                variables, *_call_args(x), rngs=rngs,
                mutable=mutable + list(_SOW_COLLECTIONS), **kwargs)
            return preds, dict(new_extra)
        preds = self.module.apply(variables, *_call_args(x), rngs=rngs,
                                  **kwargs)
        return preds, {k: variables[k] for k in mutable}


class Estimator:
    """fit/evaluate/predict over a sharded mesh.

    Args:
      model: a flax ``nn.Module`` (or any object with compatible
        init/apply), or an adapter instance.
      loss: loss name or ``fn(preds, labels) -> scalar``.
      optimizer: ZooOptimizer / optax transformation / name.
      metrics: list of Metric / names, tracked during evaluate and
        validation.
      mesh: defaults to the context mesh (data-parallel over all devices).
      clip_norm: global-L2 gradient clip (ref: tf_optimizer.py:392-396).
      clip_value: symmetric constant clip (-v, v).
      variables: pre-initialized variables (skip lazy init).
      aux_loss_collections: variable collections whose sown scalars are
        SUMMED INTO the training objective each step -- how MoE
        load-balance losses (``moe_aux_loss`` in ``losses``) reach the
        optimizer. Default: ("losses",).
    """

    def __init__(self, model, loss=None, optimizer="adam",
                 metrics: Sequence[Any] = (), mesh=None,
                 clip_norm: Optional[float] = None,
                 clip_value: Optional[float] = None,
                 variables: Optional[Dict[str, Any]] = None,
                 param_spec_fn: Optional[Callable] = None,
                 aux_loss_collections: Sequence[str] = ("losses",),
                 grad_accum_steps: int = 1,
                 seed: int = 0):
        self.adapter = (model if hasattr(model, "apply")
                        and hasattr(model, "init")
                        and not _is_flax_module(model)
                        else FlaxModelAdapter(model))
        self.loss_fn = resolve_loss(loss) if loss is not None else None
        self.tx = self._with_clipping(resolve_optimizer(optimizer),
                                      clip_norm, clip_value)
        self.metrics: List[Metric] = [resolve_metric(m) for m in metrics]
        self.mesh = mesh or default_mesh()
        self.aux_loss_collections = tuple(aux_loss_collections)
        self.param_spec_fn = param_spec_fn
        if int(grad_accum_steps) < 1:
            raise ValueError("grad_accum_steps must be >= 1")
        # k > 1 splits each fit batch into k microbatches inside the
        # jitted step (lax.scan), averaging grads before ONE optimizer
        # update: the effective batch grows k-fold at constant
        # activation memory, and the optimizer's HBM traffic (params +
        # moments read/write) amortizes over k microbatches.
        # Exact-parity caveat: batch-COUPLED layers (BatchNorm and
        # friends) see B/k rows per microbatch, so their statistics --
        # and hence the trajectory -- differ from the k=1 run; the
        # exact-parity guarantee holds for per-sample models only
        self.grad_accum_steps = int(grad_accum_steps)
        self.seed = seed
        self.variables = variables
        self.opt_state = None
        self.global_step = 0
        self.epoch = 0
        self._train_step = None
        self._eval_step = None
        self._epoch_fns: Dict[Any, Callable] = {}
        self._predict_fns: Dict[Any, Callable] = {}
        self.last_profile = None  # set by fit(profile=True)
        # what _publish_counters has published of each device counter
        self._counters_seen: Dict[Any, np.ndarray] = {}
        self._rng = training_prng_key(seed)
        from analytics_zoo_tpu.common.context import (
            enable_compilation_cache)

        enable_compilation_cache()

    # ------------------------------------------------------------- setup --
    @staticmethod
    def _with_clipping(tx, clip_norm, clip_value):
        import optax

        chain = []
        if clip_value is not None:
            chain.append(optax.clip(clip_value))
        if clip_norm is not None:
            chain.append(optax.clip_by_global_norm(clip_norm))
        chain.append(tx)
        return optax.chain(*chain) if len(chain) > 1 else tx

    def _probe_example(self, dataset: ZooDataset, batch_size: int):
        if dataset.num_samples == 0:
            raise ValueError("dataset is empty")
        x, *_ = next(dataset.batches(batch_size, shuffle=False,
                                     mesh=self.mesh, drop_remainder=False))
        return x

    def _ensure_built(self, example_x) -> None:
        newly_placed = False
        if self.variables is None:
            self._rng, init_rng = jax.random.split(self._rng)
            small = jax.tree_util.tree_map(
                lambda a: np.asarray(a)[:1], example_x)
            self.variables = self.adapter.init(init_rng, small)
            n_params = sum(np.prod(l.shape) for l in
                           jax.tree_util.tree_leaves(
                               self.variables.get("params", {})))
            logger.info("model built: %d parameters", int(n_params))
            newly_placed = True
        if self.opt_state is None:
            self.opt_state = self.tx.init(self.variables.get("params", {}))
            newly_placed = True
        if newly_placed:
            self._place_state()

    def _place_state(self) -> None:
        # default: replicate model + optimizer state over the mesh (the
        # data axis shards only the batch -- the reference's replicated
        # model-per-executor layout, Topology.scala:1145+). With
        # param_spec_fn, parameters AND optimizer moments follow the
        # given PartitionSpecs (tensor parallelism / sharded embeddings).
        if self.param_spec_fn is None:
            rep = replicated(self.mesh)
            self.variables = jax.device_put(self.variables, rep)
            self.opt_state = jax.device_put(self.opt_state, rep)
        else:
            from analytics_zoo_tpu.parallel.sharding import shard_pytree

            self.variables = shard_pytree(self.variables, self.mesh,
                                          self.param_spec_fn)
            self.opt_state = shard_pytree(self.opt_state, self.mesh,
                                          self.param_spec_fn)

    def _publish_counters(self) -> None:
        """Collection ``counters``: cumulative int32 counts that modules
        keep on the device and ``_step_math`` threads like any other
        non-parameter state (e.g. ``DroplessExperts``' assignments).
        Called where an epoch has just synced with the host anyway; the
        growth since the last call goes to the registry as
        ``zoo_model_<leaf name>_total{module=<module path>, index=<i>}``
        (``index`` counts a vector's elements; a scalar has ``0``).
        The difference is taken modulo 2**32, so a device counter may
        wrap as long as one epoch adds less than that."""
        counters = (self.variables or {}).get("counters")
        if not counters:
            return
        leaves = jax.tree_util.tree_flatten_with_path(
            jax.device_get(counters))[0]
        for path, value in leaves:
            *module, name = [str(getattr(p, "key", p)) for p in path]
            now = np.atleast_1d(np.asarray(value)).astype(np.uint32)
            seen = self._counters_seen.get(tuple(path), np.zeros_like(now))
            self._counters_seen[tuple(path)] = now
            family = _REG.counter(
                f"zoo_model_{name}_total",
                "Growth of the model's device counter of that name",
                ("module", "index"))
            for i, grown in enumerate(now - seen):
                family.labels(module="/".join(module), index=i).inc(
                    int(grown))

    # -------------------------------------------------------- train step --
    def _step_math(self, variables, opt_state, x, y, rng):
        """One SGD update; shared by the per-step and the device-cached
        whole-epoch paths. With ``grad_accum_steps`` k > 1 the batch is
        split into k microbatches scanned inside this one update.

        Every op answers to a name in a device trace: flax gives each
        module's path, JAX marks forward ops ``jvp(...)`` and backward
        ops ``transpose(jvp(...))``, and the scopes here name what no
        module does -- ``loss``, ``grad_accum`` and ``optimizer``
        (docs/observability.md, "Reading a training trace by scope").
        A scope is ``op_name`` metadata only: the compiled program is
        the same with and without it."""
        import optax

        adapter, loss_fn, tx = self.adapter, self.loss_fn, self.tx
        aux_colls = self.aux_loss_collections
        params = variables.get("params", {})
        extra = {k: v for k, v in variables.items() if k != "params"}

        def compute_loss(p, xb, yb, step_rng):
            with traced_under(self.mesh):
                preds, new_extra = adapter.apply(
                    {"params": p, **extra}, xb, training=True,
                    rng=step_rng)
            with jax.named_scope("loss"):
                loss = loss_fn(preds, yb)
                for coll in aux_colls:
                    if coll in new_extra:
                        for leaf in jax.tree_util.tree_leaves(
                                new_extra[coll]):
                            loss = loss + jnp.sum(leaf)
            # sown collections are per-step scalars, not model state
            new_extra = {k: v for k, v in new_extra.items()
                         if k not in aux_colls
                         and k not in _SOW_COLLECTIONS}
            return loss, new_extra

        k = self.grad_accum_steps
        if k <= 1:
            (loss, new_extra), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(params, x, y, rng)
        else:
            loss, new_extra, grads = self._accum_grads(
                compute_loss, params, x, y, rng, k)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return {"params": params, **new_extra}, opt_state, loss

    @staticmethod
    def _accum_grads(compute_loss, params, x, y, rng, k: int):
        """Microbatch scan: mean of per-microbatch grads == the full-
        batch gradient (losses are batch means), at 1/k the activation
        memory and one optimizer update per k microbatches. Holds
        exactly for per-sample models; batch-coupled layers (e.g.
        BatchNorm) compute statistics over B/k rows instead of B, so
        their trajectory legitimately differs from the k=1 run.

        Mutable-collection caveat: every microbatch's forward reads the
        SAME pre-step collections (``params`` is the scan's only
        threaded state), so each microbatch's mutable update -- e.g.
        the BatchNorm EMA -- is computed independently from the
        pre-step statistics, and only the LAST microbatch's update is
        kept. This is NOT equivalent to a sequential k-step loop, which
        would compound k EMA updates (each folding into the previous
        step's stats) and advance the EMA roughly k times faster."""

        def split(a):
            if a.shape[0] % k:
                raise ValueError(
                    f"grad_accum_steps={k} must divide the batch "
                    f"dim, got {a.shape[0]}")
            return a.reshape(k, a.shape[0] // k, *a.shape[1:])

        xs = jax.tree_util.tree_map(split, x)
        ys = (jax.tree_util.tree_map(split, y)
              if y is not None else None)

        def body(carry, inp):
            g_acc, loss_acc = carry
            j, xj, yj = inp
            (loss, new_extra), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(
                params, xj, yj, jax.random.fold_in(rng, j))
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, grads)
            return (g_acc, loss_acc + loss), new_extra

        with jax.named_scope("grad_accum"):
            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            (g_sum, loss_sum), extras = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)),
                (jnp.arange(k), xs, ys))
            grads = jax.tree_util.tree_map(lambda g: g / k, g_sum)
        # mutable state (e.g. batch stats): each microbatch updated from
        # the same PRE-STEP collections, so taking [-1] keeps one
        # single-microbatch update -- NOT the compounded k updates a
        # sequential k-step loop would produce (see docstring caveat)
        new_extra = jax.tree_util.tree_map(lambda a: a[-1], extras)
        return loss_sum / k, new_extra, grads

    def _build_train_step(self):
        if self._train_step is not None:
            return self._train_step
        if self.loss_fn is None:
            raise ValueError("Estimator needs a loss to train")

        def step(variables, opt_state, loss_sum, x, y, rng):
            variables, opt_state, loss = self._step_math(
                variables, opt_state, x, y, rng)
            # the epoch loss accumulates ON DEVICE: pulling per-step
            # scalars to host would sync the dispatch queue every step;
            # the epoch mean is one transfer of this resident scalar
            return variables, opt_state, loss_sum + loss, loss

        # compile-boundary instrumentation (obs.events): the first call
        # per input signature is a trace+compile -- its wall time and
        # abstract shapes land in the event log and feed the
        # recompile-storm detector (a fit() whose batches keep changing
        # shape recompiles every step and warns instead of crawling)
        self._train_step = instrument_compiles(
            jax.jit(step, donate_argnums=(0, 1, 2)),
            "estimator.train_step", subsystem="learn")
        return self._train_step

    def _build_epoch_fn(self, batch_size: int, n_steps: int,
                        n_samples: int):
        """Whole-epoch train function for device-resident datasets: ONE
        dispatch runs ``n_steps`` updates via ``lax.fori_loop``, gathering
        each shuffled batch on device. Where the reference runs two Spark
        jobs per ITERATION (Topology.scala:1193+), this runs one XLA
        program per EPOCH -- no host round-trips inside. The shuffle
        permutation is drawn ON DEVICE too: only an rng key crosses the
        host boundary per epoch (a host-built permutation of a
        MovieLens-scale epoch is ~17 MB of transfer)."""
        from jax.sharding import NamedSharding

        mesh = self.mesh

        def epoch(variables, opt_state, x_all, y_all, rng0):
            perm_rng, step_rng0 = jax.random.split(rng0)
            perm = jax.random.permutation(perm_rng, n_samples)

            def body(i, carry):
                variables, opt_state, loss_sum = carry
                idx = jax.lax.dynamic_slice_in_dim(
                    perm, i * batch_size, batch_size)

                def take(a):
                    b = jnp.take(a, idx, axis=0)
                    return jax.lax.with_sharding_constraint(
                        b, NamedSharding(
                            mesh, sharding.data_parallel_spec(b)))

                x = jax.tree_util.tree_map(take, x_all)
                y = (jax.tree_util.tree_map(take, y_all)
                     if y_all is not None else None)
                rng = jax.random.fold_in(step_rng0, i)
                variables, opt_state, loss = self._step_math(
                    variables, opt_state, x, y, rng)
                return variables, opt_state, loss_sum + loss

            init = (variables, opt_state, jnp.zeros((), jnp.float32))
            variables, opt_state, loss_sum = jax.lax.fori_loop(
                0, n_steps, body, init)
            return variables, opt_state, loss_sum / n_steps

        return instrument_compiles(
            jax.jit(epoch, donate_argnums=(0, 1)),
            "estimator.epoch", subsystem="learn")

    def _eval_metrics(self) -> List[Metric]:
        """The tracked metrics plus a Loss metric when a loss is set."""
        out = list(self.metrics)
        if self.loss_fn is not None:
            from analytics_zoo_tpu.learn.metrics import Loss

            out.append(Loss(self.loss_fn))
        return out

    def _build_eval_step(self):
        if self._eval_step is not None:
            return self._eval_step
        adapter = self.adapter
        metrics = self._eval_metrics()
        aux_colls = self.aux_loss_collections
        # only the flax adapter can surface sown aux losses; other
        # adapters (GraphModel, custom) have none to surface
        want_sown = bool(aux_colls) and isinstance(adapter,
                                                   FlaxModelAdapter)

        from analytics_zoo_tpu.learn.metrics import Loss

        def step(variables, x, y, w, states):
            with traced_under(self.mesh):
                preds, extra = adapter.apply(
                    variables, x, training=False,
                    **({"want_sown": True} if want_sown else {}))
            aux = None
            if want_sown:
                aux = jnp.zeros((), jnp.float32)
                for coll in aux_colls:
                    for leaf in jax.tree_util.tree_leaves(
                            extra.get(coll, {})):
                        aux = aux + jnp.sum(leaf)
            out = []
            for m, s in zip(metrics, states):
                s = m.update(s, preds, y, weights=w)
                if aux is not None and isinstance(m, Loss):
                    # the aux term applies once per sample so the
                    # streaming mean matches the training objective
                    # (keras semantics: regularizers count in val_loss)
                    wsum = (jnp.sum(jnp.asarray(w, jnp.float32))
                            if w is not None else
                            jnp.asarray(_batch_size_of(preds),
                                        jnp.float32))
                    s = {**s, "total": s["total"] + aux * wsum}
                out.append(s)
            return out

        self._eval_step = instrument_compiles(
            jax.jit(step), "estimator.eval_step", subsystem="learn")
        return self._eval_step

    # --------------------------------------------------------------- fit --
    def fit(self, data, batch_size: int, epochs: int = 1,
            validation_data=None, validation_trigger: Optional[Trigger] = None,
            checkpoint_dir: Optional[str] = None,
            checkpoint_trigger: Optional[Trigger] = None,
            log_dir: Optional[str] = None,
            resume: bool = False,
            device_cache: bool = False,
            profile: bool = False,
            trace_dir: Optional[str] = None) -> List[Dict[str, float]]:
        """Train; returns per-epoch history.

        Failure semantics mirror InternalDistriOptimizer.train
        (ref: Topology.scala:1255-1332): on an exception mid-epoch, if a
        checkpoint exists and fewer than ``zoo.train.failure.retry_times``
        failures occurred within ``zoo.train.failure.retry_interval_s``,
        restore the latest snapshot and continue.

        ``device_cache=True`` places the whole dataset in device memory
        once and compiles each epoch into a single XLA program (shuffled
        batches gathered on device) -- the fast path for datasets that
        fit in HBM. Triggers/validation/checkpoints then run at epoch
        granularity, and single-process only.

        ``profile=True`` records data-wait vs step-dispatch stage timers
        into ``self.last_profile`` (a ``TrainingProfiler``; the Ray
        runners' profile=True analog, ref: pytorch_ray_estimator.py:
        150-190); ``trace_dir`` additionally captures a jax.profiler
        device trace viewable in TensorBoard, and writes the call's
        spans beside it (``fit_spans.<trace id>.trace.json``, Chrome
        trace JSON on the same clock).

        Every call records its spans in the process's span ring
        (``obs.tracing.get_tracer()``), with no switch: ``fit`` itself,
        ``fit_prepare``, each step's ``data_wait`` and ``train_step``
        (and the producer thread's ``host_batch`` and ``shard_batch``)
        with the step's index ``i``, ``log_sync``, ``epoch_sync`` and
        ``publish_counters``, under one ``trace_id`` a call
        (docs/observability.md "Training spans").
        """
        call = _FitCall()
        try:
            cfg = get_config()
            dataset = _as_dataset(data)
            val_dataset = (_as_dataset(validation_data)
                           if validation_data is not None else None)
            validation_trigger = validation_trigger or EveryEpoch()
            checkpoint_trigger = checkpoint_trigger or EveryEpoch()
            self._ensure_built(self._probe_example(dataset, batch_size))
            if resume and checkpoint_dir and \
                    ckpt_lib.latest_step(checkpoint_dir) is not None:
                self._restore(checkpoint_dir)
            profiler = None
            if profile or trace_dir:
                from analytics_zoo_tpu.learn.profiler import TrainingProfiler

                profiler = call.profiler = TrainingProfiler(
                    trace_dir=trace_dir)
                self.last_profile = profiler
                profiler.start_trace()
            emit("train_start", "learn", epochs=epochs,
                 batch_size=batch_size, device_cache=bool(device_cache))
            try:
                if device_cache:
                    if jax.process_count() > 1:
                        raise ValueError("device_cache supports "
                                         "single-process runs only")
                    return self._fit_device_cached(
                        dataset, val_dataset, batch_size, epochs,
                        validation_trigger, checkpoint_trigger,
                        checkpoint_dir, log_dir, call)

                train_step = self._build_train_step()
                writer = self._make_writer(log_dir)
                log_every = cfg.get("zoo.train.log_every_n_steps")
                retry_times = cfg.get("zoo.train.failure.retry_times")
                retry_interval = cfg.get("zoo.train.failure.retry_interval_s")
                failures: List[float] = []
                history: List[Dict[str, float]] = []
                state = TriggerState(epoch=self.epoch,
                                     iteration=self.global_step)
                steps_per_epoch = dataset.steps_per_epoch(batch_size)
                try:
                    return self._fit_loop(
                        dataset, val_dataset, batch_size, epochs, train_step,
                        writer, log_every, retry_times, retry_interval,
                        validation_trigger, checkpoint_trigger,
                        checkpoint_dir, failures, history, state,
                        steps_per_epoch, call)
                finally:
                    if writer:
                        writer.close()
            finally:
                emit("train_stop", "learn", epochs_run=self.epoch,
                     global_step=self.global_step)
                if profiler is not None:
                    profiler.stop_trace()
                    logger.info("training profile: %s", profiler.summary())
        finally:
            call.span("fit", call.t0, time.perf_counter(),
                      epoch=self.epoch, steps=call.i)
            if trace_dir:
                call.tracer.dump_chrome_trace(
                    os.path.join(trace_dir,
                                 f"fit_spans.{call.trace_id}.trace.json"),
                    call.trace_id)

    def _fit_loop(self, dataset, val_dataset, batch_size, epochs,
                  train_step, writer, log_every, retry_times,
                  retry_interval, validation_trigger, checkpoint_trigger,
                  checkpoint_dir, failures, history, state,
                  steps_per_epoch, call: _FitCall
                  ) -> List[Dict[str, float]]:
        while self.epoch < epochs:
            epoch_start = time.perf_counter()
            # placed like the step's own loss_sum output: an unplaced
            # scalar has another type than the mesh-resident one that
            # comes back, and step 2 would trace and compile again
            loss_sum = jax.device_put(jnp.zeros((), jnp.float32),
                                      replicated(self.mesh))
            n_steps = 0
            last_val: Optional[Dict[str, float]] = None
            try:
                batches = iter(dataset.device_iterator(
                    batch_size, mesh=self.mesh, shuffle=True,
                    seed=self.seed, epoch=self.epoch,
                    spans=(call.trace_id, call.i)))
                call.prepared()
                for step_in_epoch in range(steps_per_epoch):
                    with _stage(call, "data_wait", i=call.i):
                        try:
                            x, y = next(batches)
                        except StopIteration:
                            break
                    self._rng, step_rng = jax.random.split(self._rng)
                    with _stage(call, "train_step", i=call.i):
                        (self.variables, self.opt_state, loss_sum,
                         loss) = train_step(self.variables,
                                            self.opt_state, loss_sum,
                                            x, y, step_rng)
                    self.global_step += 1
                    n_steps += 1
                    _M_STEPS.inc()
                    if (self.global_step % log_every == 0 or
                            self.global_step == 1):
                        with _stage(call, "log_sync", i=call.i):
                            lf = float(loss)
                        # loss reaches triggers at log cadence only: a
                        # per-step float() would force a host sync every
                        # step and kill async dispatch
                        state.loss = lf
                        logger.info("epoch %d step %d loss %.5f",
                                    self.epoch, self.global_step, lf)
                        if writer:
                            writer.add_scalar("train/loss", lf,
                                              self.global_step)
                    # triggers see every optimization step (the contract of
                    # triggers.py; makes SeveralIteration/MinLoss live).
                    # epoch boundaries count steps *within* this epoch, so
                    # they stay correct after a mid-epoch restore shifts
                    # global_step off the modulo grid.
                    call.i += 1
                    finishing = step_in_epoch == steps_per_epoch - 1
                    state.iteration = self.global_step
                    state.epoch = self.epoch + (1 if finishing else 0)
                    state.epoch_finished = finishing
                    state.wall_time = time.time()
                    if val_dataset is not None and validation_trigger(state):
                        last_val = self.evaluate(val_dataset, batch_size)
                        state.score = next(iter(last_val.values()), None)
                        if writer:
                            for k, v in last_val.items():
                                writer.add_scalar(f"validation/{k}", v,
                                                  self.global_step)
                    if checkpoint_dir is not None and \
                            checkpoint_trigger(state):
                        ckpt_lib.save_checkpoint(
                            checkpoint_dir, self.variables, self.opt_state,
                            self.global_step, state.epoch)
                # epoch completed; ONE host sync for the whole epoch
                self.epoch += 1
                _M_EPOCHS.inc()
                state.epoch = self.epoch
                with _stage(call, "epoch_sync", epoch=self.epoch) as sync:
                    mean_loss = (float(loss_sum) / n_steps if n_steps
                                 else float("nan"))
                entry: Dict[str, float] = {
                    "epoch": self.epoch,
                    "loss": mean_loss,
                    "seconds": sync.t1 - epoch_start,
                }
                with _stage(call, "publish_counters"):
                    self._publish_counters()
                if last_val is not None:
                    entry.update({f"val_{k}": v for k, v in last_val.items()})
                history.append(entry)
                logger.info("epoch %d done: %s", self.epoch, entry)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                if not self._handle_training_failure(
                        e, failures, retry_times, retry_interval,
                        checkpoint_dir, state):
                    raise
        return history

    def _handle_training_failure(self, e, failures, retry_times,
                                 retry_interval, checkpoint_dir,
                                 state) -> bool:
        """Shared retry-from-checkpoint contract for both fit loops
        (ref: Topology.scala:1255-1332): prune the failure window, and
        if a checkpoint exists within the retry budget, reset stale
        trigger state and restore. Returns whether training continues
        (False -> caller re-raises)."""
        now = time.time()
        failures[:] = [t for t in failures
                       if now - t < retry_interval] + [now]
        can_retry = (checkpoint_dir is not None and
                     ckpt_lib.latest_step(checkpoint_dir) is not None
                     and len(failures) <= retry_times)
        logger.exception("training failure %d/%d in window: %s",
                         len(failures), retry_times, e)
        emit("train_failure", "learn", error=repr(e),
             failures=len(failures), retrying=can_retry)
        if not can_retry:
            return False
        # the restored model's loss/score are unknown until the next
        # log step / validation; stale pre-crash values would misfire
        # MinLoss/MaxScore
        state.loss = None
        state.score = None
        self._restore(checkpoint_dir)
        return True

    @staticmethod
    def _make_writer(log_dir: Optional[str]):
        if log_dir is None:
            return None
        from analytics_zoo_tpu.utils.summary import SummaryWriter

        return SummaryWriter(log_dir)

    @staticmethod
    def _fired_in_range(trigger: Trigger, state: TriggerState,
                        start_step: int, end_step: int) -> bool:
        """Whether ``trigger`` would have fired at ANY step in
        (start_step, end_step] -- the cached path checks triggers once
        per epoch, so step-granular triggers (SeveralIteration) must
        scan the epoch's step range instead of testing only the final
        step (which is always a multiple of steps-per-epoch)."""
        saved = state.iteration
        try:
            for it in range(start_step + 1, end_step + 1):
                state.iteration = it
                if trigger(state):
                    return True
            return False
        finally:
            state.iteration = saved

    def _fit_device_cached(self, dataset, val_dataset, batch_size,
                           epochs, validation_trigger, checkpoint_trigger,
                           checkpoint_dir, log_dir, call: _FitCall
                           ) -> List[Dict[str, float]]:
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_config()
        n = dataset.num_samples
        n_steps = n // batch_size
        if n_steps == 0:
            raise ValueError(f"dataset ({n} samples) smaller than "
                             f"batch_size {batch_size}")
        rep = NamedSharding(self.mesh, P())
        x_all = jax.device_put(
            jax.tree_util.tree_map(np.asarray, dataset.features), rep)
        y_all = (jax.device_put(
            jax.tree_util.tree_map(np.asarray, dataset.labels), rep)
            if dataset.labels is not None else None)
        key = (batch_size, n_steps, n)
        epoch_fn = self._epoch_fns.get(key)
        if epoch_fn is None:
            epoch_fn = self._build_epoch_fn(batch_size, n_steps, n)
            self._epoch_fns[key] = epoch_fn
        writer = self._make_writer(log_dir)
        history: List[Dict[str, float]] = []
        state = TriggerState(epoch=self.epoch, iteration=self.global_step)
        retry_times = cfg.get("zoo.train.failure.retry_times")
        retry_interval = cfg.get("zoo.train.failure.retry_interval_s")
        failures: List[float] = []
        try:
            while self.epoch < epochs:
                t0 = time.time()
                step_before = self.global_step
                try:
                    self._rng, erng = jax.random.split(self._rng)
                    with _stage(call, "train_step"):
                        (self.variables, self.opt_state,
                         mean_loss) = epoch_fn(
                            self.variables, self.opt_state, x_all,
                            y_all, erng)
                        lf = float(mean_loss)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    if not self._handle_training_failure(
                            e, failures, retry_times, retry_interval,
                            checkpoint_dir, state):
                        raise
                    continue
                self.epoch += 1
                self.global_step += n_steps
                call.i += n_steps
                _M_EPOCHS.inc()
                _M_STEPS.inc(n_steps)
                self._publish_counters()
                entry: Dict[str, float] = {
                    "epoch": self.epoch, "loss": lf,
                    "seconds": time.time() - t0}
                state.epoch = self.epoch
                state.iteration = self.global_step
                state.loss = lf
                state.epoch_finished = True
                state.wall_time = time.time()
                if writer:
                    writer.add_scalar("train/loss", lf, self.global_step)
                if val_dataset is not None and self._fired_in_range(
                        validation_trigger, state, step_before,
                        self.global_step):
                    val = self.evaluate(val_dataset, batch_size)
                    state.score = next(iter(val.values()), None)
                    entry.update({f"val_{k}": v for k, v in val.items()})
                    if writer:
                        for k, v in val.items():
                            writer.add_scalar(f"validation/{k}", v,
                                              self.global_step)
                if checkpoint_dir is not None and self._fired_in_range(
                        checkpoint_trigger, state, step_before,
                        self.global_step):
                    ckpt_lib.save_checkpoint(
                        checkpoint_dir, self.variables, self.opt_state,
                        self.global_step, self.epoch)
                history.append(entry)
                logger.info("epoch %d done (device-cached): %s",
                            self.epoch, entry)
        finally:
            if writer:
                writer.close()
        return history

    def _restore(self, checkpoint_dir: str) -> None:
        # templates carry structure + shape/dtype only: live arrays may
        # already be invalid (donated buffers after a mid-step failure)
        def to_struct(a):
            if hasattr(a, "shape") and hasattr(a, "dtype"):
                return jax.ShapeDtypeStruct(a.shape, a.dtype)
            return a

        var_t = jax.tree_util.tree_map(to_struct, self.variables)
        opt_t = jax.tree_util.tree_map(to_struct, self.opt_state)
        self.variables, self.opt_state, meta = ckpt_lib.load_checkpoint(
            checkpoint_dir, var_t, opt_t)
        self.global_step = meta["step"]
        self.epoch = meta["epoch"]
        self._place_state()
        logger.info("restored from checkpoint: step=%d epoch=%d",
                    self.global_step, self.epoch)

    # ---------------------------------------------------------- evaluate --
    def evaluate(self, data, batch_size: int) -> Dict[str, float]:
        """Metrics over the full dataset -- the short final batch is
        included via padding + masking, so no tail samples are dropped."""
        dataset = _as_dataset(data)
        self._ensure_built(self._probe_example(dataset, batch_size))
        eval_step = self._build_eval_step()
        metrics = self._eval_metrics()
        states: List[Any] = [m.empty() for m in metrics]
        for x, y, w in dataset.device_iterator(
                batch_size, mesh=self.mesh, shuffle=False,
                drop_remainder=False, with_mask=True):
            states = eval_step(self.variables, x, y, w, states)
        return {m.name: float(m.result(s))
                for m, s in zip(metrics, states)}

    # ----------------------------------------------------------- predict --
    def predict(self, data, batch_size: int = 32) -> Any:
        dataset = _as_dataset(data, labeled=False)
        self._ensure_built(self._probe_example(dataset, batch_size))
        adapter = self.adapter

        def forward(variables, x):
            with traced_under(self.mesh):
                return adapter.apply(variables, x, training=False)[0]

        if "predict" not in self._predict_fns:
            self._predict_fns["predict"] = instrument_compiles(
                jax.jit(forward), "estimator.predict", subsystem="learn")
        fn = self._predict_fns["predict"]

        # globally-sharded outputs are not fully addressable per host;
        # gather_to_host all-gathers them (batch order is preserved
        # because batches() hands each process its contiguous block)
        outs: List[Any] = []
        for x, _ in dataset.device_iterator(batch_size, mesh=self.mesh,
                                            shuffle=False,
                                            drop_remainder=False):
            outs.append(sharding.gather_to_host(fn(self.variables, x)))
        result = jax.tree_util.tree_map(
            lambda *parts: np.concatenate(parts)[:dataset.num_samples],
            *outs)
        return result

    # ------------------------------------------------------- persistence --
    def save(self, ckpt_dir: str) -> None:
        self._ensure_opt_for_save()
        ckpt_lib.save_checkpoint(ckpt_dir, self.variables, self.opt_state,
                                 self.global_step, self.epoch)

    def _ensure_opt_for_save(self):
        if self.variables is None:
            raise ValueError("nothing to save: model not built")
        if self.opt_state is None:
            self.opt_state = self.tx.init(self.variables.get("params", {}))

    def load(self, ckpt_dir: str) -> None:
        """Restore weights; works on an un-built Estimator (the model
        variables restore template-free, then the optimizer state restores
        against a fresh tx.init template)."""
        if self.variables is None:
            self.variables, _, _ = ckpt_lib.load_checkpoint(ckpt_dir, None,
                                                            None)
        self._ensure_opt_for_save()
        self._restore(ckpt_dir)


def recompiled(old: Optional["Estimator"], model, **kwargs) -> "Estimator":
    """Build a fresh Estimator carrying over trained weights + counters
    from ``old`` (the Keras compile() contract: recompiling changes the
    training config, not the model)."""
    est = Estimator(model,
                    variables=old.variables if old is not None else None,
                    **kwargs)
    if old is not None:
        est.global_step = old.global_step
        est.epoch = old.epoch
        est._counters_seen = old._counters_seen
    return est


def _batch_size_of(preds) -> int:
    leaf = jax.tree_util.tree_leaves(preds)[0]
    return leaf.shape[0]


def _is_flax_module(obj) -> bool:
    try:
        import flax.linen as nn

        return isinstance(obj, nn.Module)
    except ImportError:  # pragma: no cover
        return False
