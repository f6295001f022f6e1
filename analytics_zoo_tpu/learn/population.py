"""PopulationEstimator: N models as ONE XLA program.

The TPU-native inversion of the reference's one-trial-per-Ray-worker
AutoML shape (ref: pyzoo/zoo/automl/search/ray_tune_search_engine.py):
instead of N processes each fitting one model, N parameter trees are
stacked along a leading *member* axis and trained by a single jitted
``jax.vmap`` step. Hyperparameters that only scale the update --
learning rate and (decoupled) weight decay -- ride as traced per-lane
scalars, so one compiled executable covers every member's setting.

Member *masking* keeps shapes fixed across a search: a culled lane
trains at zero effective lr with its parameters/optimizer state frozen
by a select, rather than being removed from the stack -- ASHA rung
promotion never changes array shapes, so it never recompiles.

Per-member training replays the exact per-member semantics of
:class:`~analytics_zoo_tpu.learn.estimator.Estimator`'s per-step fit
path (same PRNG stream: one split at init, one split per step; same
epoch-seeded host-side shuffle; same Adam update), so a lane's
trajectory matches what a solo ``Estimator(seed=s)`` run of the same
config produces -- the property the vectorized AutoML executor's
parity gate (`tests/test_vectorized_search.py`) enforces.

All data arguments carry the member axis: ``x`` is ``[N, B, ...]``
(use :meth:`PopulationEstimator.stack_data` to broadcast shared data).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.common.log import get_logger
from analytics_zoo_tpu.learn.estimator import (
    _SOW_COLLECTIONS, FlaxModelAdapter, _is_flax_module)
from analytics_zoo_tpu.learn.objectives import resolve_loss
from analytics_zoo_tpu.obs.events import instrument_compiles
from analytics_zoo_tpu.obs.metrics import get_registry

logger = get_logger(__name__)

_M_PSTEPS = get_registry().counter(
    "zoo_population_steps_total",
    "Vectorized population train steps (one step updates every lane)")
_M_PMEMBERS = get_registry().gauge(
    "zoo_population_members_items",
    "Member lanes in the most recently built population")
_M_PMASKED = get_registry().gauge(
    "zoo_population_masked_items",
    "Masked (frozen) lanes in the most recently used population")


def _shuffle_order(seed: int, epoch: int, n: int) -> np.ndarray:
    """The Estimator fit path's epoch permutation, verbatim
    (ZooDataset.batches): parity depends on byte-identical batch
    order, so the constant is shared by construction, not by copy."""
    rng = np.random.RandomState((seed * 100003 + epoch) & 0x7FFFFFFF)
    return rng.permutation(n)


class PopulationEstimator:
    """Train/eval N stacked models with one compiled vmapped step.

    Args:
      model: a flax module (shared architecture for every member) or a
        prebuilt adapter with ``init``/``apply``.
      n_members: lane count N (inferred from ``lr``/``seeds`` arrays).
      loss: loss name or ``fn(preds, labels) -> scalar``.
      lr: scalar or ``[N]`` per-lane learning rates (traced, not
        compiled in: changing a lane's lr never recompiles).
      weight_decay: scalar or ``[N]`` decoupled weight decay lanes.
      beta_1 / beta_2 / epsilon: Adam moments config (matches
        ``learn.optim.Adam`` defaults so a lane reproduces
        ``Estimator(optimizer=Adam(lr))`` exactly).
      seeds: ``[N]`` per-member init/dropout seeds (vmapped seeded
        init). Default: every lane seed 0 -- the Estimator default, so
        AutoML lanes that differ only in lr share the solo path's init.
      aux_loss_collections: sown collections summed into the training
        objective per step (same contract as Estimator).
    """

    def __init__(self, model, n_members: Optional[int] = None,
                 loss: Any = "mse", lr: Any = 1e-3,
                 weight_decay: Any = 0.0, beta_1: float = 0.9,
                 beta_2: float = 0.999, epsilon: float = 1e-8,
                 seeds: Optional[Sequence[int]] = None,
                 aux_loss_collections: Sequence[str] = ("losses",)):
        self.adapter = (model if hasattr(model, "apply")
                        and hasattr(model, "init")
                        and not _is_flax_module(model)
                        else FlaxModelAdapter(model))
        self.loss_fn = resolve_loss(loss)
        lr_arr = np.atleast_1d(np.asarray(lr, np.float32))
        wd_arr = np.atleast_1d(np.asarray(weight_decay, np.float32))
        n = n_members or max(len(lr_arr), len(wd_arr),
                             len(seeds) if seeds is not None else 1)
        cap = int(get_config().get("zoo.population.max_members", 1024))
        if n < 1 or n > cap:
            raise ValueError(
                f"population needs 1..{cap} members, got {n} "
                "(raise zoo.population.max_members to go bigger)")
        self.n_members = n
        self.lr = jnp.broadcast_to(jnp.asarray(lr_arr), (n,))
        self.weight_decay = jnp.broadcast_to(jnp.asarray(wd_arr), (n,))
        self.beta_1, self.beta_2, self.epsilon = beta_1, beta_2, epsilon
        self.seeds = (list(seeds) if seeds is not None else [0] * n)
        if len(self.seeds) != n:
            raise ValueError(f"seeds must have {n} entries")
        self.aux_loss_collections = tuple(aux_loss_collections)
        # shuffle stream seed -- Estimator's ``seed`` ctor arg; lanes
        # share one epoch permutation (solo runs all use seed=0 too)
        self.shuffle_seed = 0
        self.mask = jnp.ones((n,), jnp.float32)
        self.epoch = 0
        self.variables = None   # stacked: every leaf is [N, ...]
        self.opt_state = None
        self._rngs = None       # [N] per-lane training PRNG keys
        self._train_step = None
        self._predict_fn = None
        import optax

        self._core = optax.scale_by_adam(
            b1=beta_1, b2=beta_2, eps=epsilon)
        _M_PMEMBERS.set(float(n))

    # ------------------------------------------------------------ data --
    @staticmethod
    def stack_data(x, n: int):
        """Broadcast shared (memberless) data to the ``[N, ...]``
        layout every fit/predict argument uses."""
        return jax.tree_util.tree_map(
            lambda a: np.broadcast_to(
                np.asarray(a)[None], (n,) + np.asarray(a).shape), x)

    # ----------------------------------------------------------- build --
    def _ensure_built(self, example_x) -> None:
        if self.variables is not None:
            return
        # per-lane stream: PRNGKey(seed) then ONE split -- row 0 carries
        # on as the training stream, row 1 initializes (the exact
        # Estimator._ensure_built sequence, per lane)
        keys0 = jnp.stack([jax.random.PRNGKey(int(s))
                           for s in self.seeds])
        both = jax.vmap(jax.random.split)(keys0)
        self._rngs, init_rngs = both[:, 0], both[:, 1]
        small = jax.tree_util.tree_map(
            lambda a: np.asarray(a)[:, :1], example_x)
        self.variables = jax.vmap(
            lambda k, xs: self.adapter.init(k, xs))(init_rngs, small)
        self.opt_state = jax.vmap(self._core.init)(
            self.variables.get("params", {}))
        n_params = sum(int(np.prod(l.shape)) for l in
                       jax.tree_util.tree_leaves(
                           self.variables.get("params", {})))
        logger.info("population built: %d members, %d stacked params",
                    self.n_members, n_params)

    # ------------------------------------------------------ train step --
    def _member_step(self, variables, opt_state, x, y, rng, lr, wd,
                     mask):
        """One member's SGD update -- Estimator._step_math with the lr
        applied per-lane (the optimizer core is lr-free scale_by_adam;
        ``optax.adam(lr)`` is exactly that core followed by a -lr
        scale, so a lane reproduces the solo Adam trajectory)."""
        import optax

        adapter, loss_fn = self.adapter, self.loss_fn
        aux_colls = self.aux_loss_collections
        new_rng, step_rng = jax.random.split(rng)
        params = variables.get("params", {})
        extra = {k: v for k, v in variables.items() if k != "params"}

        def compute_loss(p, xb, yb, srng):
            preds, new_extra = adapter.apply(
                {"params": p, **extra}, xb, training=True, rng=srng)
            loss = loss_fn(preds, yb)
            for coll in aux_colls:
                if coll in new_extra:
                    for leaf in jax.tree_util.tree_leaves(
                            new_extra[coll]):
                        loss = loss + jnp.sum(leaf)
            new_extra = {k: v for k, v in new_extra.items()
                         if k not in aux_colls
                         and k not in _SOW_COLLECTIONS}
            return loss, new_extra

        (loss, new_extra), grads = jax.value_and_grad(
            compute_loss, has_aux=True)(params, x, y, step_rng)
        updates, new_opt = self._core.update(grads, opt_state, params)
        lr_eff = lr * mask
        updates = jax.tree_util.tree_map(
            lambda u, p: -lr_eff * (u + wd * p), updates, params)
        new_params = optax.apply_updates(params, updates)
        # a masked lane is FROZEN, not merely zero-stepped: optimizer
        # moments and mutable collections hold too, so unmasking (or
        # exporting) later sees exactly the state at mask time
        keep = mask > 0

        def sel(new, old):
            return jnp.where(keep, new, old)

        new_vars = {"params": jax.tree_util.tree_map(
            lambda n_, o: sel(n_, o), new_params, params)}
        for k, v in new_extra.items():
            new_vars[k] = jax.tree_util.tree_map(
                lambda n_, o: sel(n_, o), v, extra[k])
        for k, v in extra.items():
            new_vars.setdefault(k, v)
        new_opt = jax.tree_util.tree_map(
            lambda n_, o: sel(n_, o), new_opt, opt_state)
        return new_vars, new_opt, loss, new_rng

    def _build_train_step(self):
        if self._train_step is not None:
            return self._train_step
        stepv = jax.vmap(self._member_step)

        def step(variables, opt_state, x, y, rngs, lr, wd, mask):
            return stepv(variables, opt_state, x, y, rngs, lr, wd,
                         mask)

        self._train_step = instrument_compiles(
            jax.jit(step, donate_argnums=(0, 1)),
            "population.train_step", subsystem="learn")
        return self._train_step

    # ------------------------------------------------------------- fit --
    def fit(self, x, y, batch_size: int, epochs: int,
            budgets: Optional[Sequence[int]] = None) -> List[np.ndarray]:
        """Train every unmasked lane from ``self.epoch`` up to
        ``epochs`` (absolute, the Estimator.fit convention). ``x``/``y``
        are member-stacked ``[N, B, ...]`` arrays; every lane sees the
        same epoch permutation (shared shuffle seed) over its own data
        lane. ``budgets`` gives per-lane absolute epoch targets: a lane
        freezes once ``epoch >= budget`` (fixed-shape ASHA masking --
        heterogeneous epoch budgets train lockstep without reshaping).
        Returns per-epoch mean-loss vectors ``[N]``."""
        x = np.asarray(x)
        y = np.asarray(y)
        n = self.n_members
        if x.shape[0] != n or y.shape[0] != n:
            raise ValueError(
                f"x/y must be member-stacked [N={n}, B, ...]; got "
                f"{x.shape} / {y.shape}")
        n_samples = x.shape[1]
        batch_size = max(1, min(int(batch_size), n_samples))
        self._ensure_built(x)
        step = self._build_train_step()
        budget_arr = (np.asarray(budgets, np.int32)
                      if budgets is not None else None)
        history: List[np.ndarray] = []
        steps_per_epoch = n_samples // batch_size
        while self.epoch < epochs:
            mask = self.mask
            if budget_arr is not None:
                mask = mask * jnp.asarray(
                    (budget_arr > self.epoch).astype(np.float32))
            _M_PMASKED.set(float(n - int(jnp.sum(mask > 0))))
            order = _shuffle_order(self.shuffle_seed, self.epoch,
                                   n_samples)
            losses = np.zeros((n,), np.float32)
            for b in range(steps_per_epoch):
                idx = order[b * batch_size:(b + 1) * batch_size]
                xb, yb = x[:, idx], y[:, idx]
                (self.variables, self.opt_state, loss,
                 self._rngs) = step(self.variables, self.opt_state,
                                    xb, yb, self._rngs, self.lr,
                                    self.weight_decay, mask)
                _M_PSTEPS.inc()
                losses = losses + np.asarray(loss)
            history.append(losses / max(steps_per_epoch, 1))
            self.epoch += 1
        return history

    # ----------------------------------------------------- eval / mask --
    def predict(self, x) -> np.ndarray:
        """Vmapped inference apply: ``[N, B, ...]`` -> stacked member
        predictions (one dispatch for the whole population)."""
        self._ensure_built(x)
        if self._predict_fn is None:
            adapter = self.adapter

            def pred(variables, xb):
                out, _ = adapter.apply(variables, xb, training=False)
                return out

            self._predict_fn = instrument_compiles(
                jax.jit(jax.vmap(pred)), "population.predict",
                subsystem="learn")
        return np.asarray(self._predict_fn(
            self.variables, jnp.asarray(np.asarray(x))))

    def ensemble_predict(self, x):
        """Shared-input ensemble: every member answers the SAME batch;
        returns ``(mean, variance)`` over the member axis -- the
        population variance is the confidence signal the reference
        model zoo's anomaly-detection scenario thresholds on."""
        stacked = self.stack_data(np.asarray(x), self.n_members)
        preds = self.predict(stacked)
        return preds.mean(axis=0), preds.var(axis=0)

    def set_mask(self, mask) -> None:
        """``[N]`` 0/1 lane mask; 0 freezes a lane (zero effective lr
        AND held optimizer/mutable state). Shapes never change, so
        re-masking never recompiles."""
        mask = np.asarray(mask, np.float32).reshape(self.n_members)
        self.mask = jnp.asarray(mask)
        _M_PMASKED.set(float(np.sum(mask <= 0)))

    # ---------------------------------------------------------- export --
    def export_member(self, i: int) -> Dict[str, Any]:
        """Member ``i`` as a plain (unstacked) variables tree --
        drop-in for ``Estimator.variables`` / flax serialization."""
        if self.variables is None:
            raise RuntimeError("population not built; fit() first")
        if not 0 <= i < self.n_members:
            raise IndexError(f"member {i} out of range")
        return jax.device_get(jax.tree_util.tree_map(
            lambda a: a[i], self.variables))

    def export_member_bytes(self, i: int) -> bytes:
        """Member ``i`` serialized exactly like
        ``TimeSequenceModel.state_bytes`` (flax ``to_bytes`` of the
        variables tree), so vectorized trial outputs rebuild through
        the same ``load_state_bytes`` path as pool-trial outputs."""
        from flax.serialization import to_bytes

        return to_bytes(self.export_member(i))
