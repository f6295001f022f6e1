"""DecodeEngine: prefill/decode split over a paged KV cache.

The generation analog of ``InferenceModel``'s bucketed predict path,
split the way the workload splits:

- **Prefill** is compute-bound and ragged: prompts are padded onto a
  *prompt-length ladder* (``prefill_ladder`` -- page-size-aligned
  powers of two, so every bucket scatters into whole pages) and run
  through the model's full causal forward, one jitted program per
  bucket. Same discipline as the predict bucket cache: ``warm_up``
  walks the ladder under ``obs.events.warming()`` and every live
  compile feeds the recompile-storm detector.
- **Decode** is memory-bound and regular: ONE fixed-shape jitted step
  advances every active slot of the slot table by one token --
  requests joining or leaving the running batch never mint a new XLA
  shape, which is what makes continuous batching tractable on TPU at
  all (ROADMAP "autoregressive generation serving").

The engine owns slot *state* (next input token, write position per
slot); :class:`~analytics_zoo_tpu.inference.kv_cache.PagedKVCache`
owns page *accounting*; request metadata (uri, deadline, budget) is
the worker's business. Greedy sampling (argmax) runs inside the jitted
step so only S int32 tokens cross to the host per step, and the host
sync lives in ``_finalize_*`` methods -- the declared hot-path barrier
deepcheck's ``hotpath-block-on-device`` rule checks against.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from analytics_zoo_tpu.common.log import get_logger
from analytics_zoo_tpu.inference.kv_cache import CacheOverflow, PagedKVCache
from analytics_zoo_tpu.obs.events import record_compile, warming
from analytics_zoo_tpu.obs.metrics import get_registry
from analytics_zoo_tpu.serving.generation.model import (
    GenModelConfig, TinyGenLM)

logger = get_logger(__name__)

# deepcheck hot-path roots (docs/zoolint.md "deepcheck"): the decode
# loop and prefill are the generation data plane's per-token /
# per-request device paths -- host blocking syncs belong behind the
# _finalize_* barrier, not inline
ZOOLINT_HOT_PATH = ("DecodeEngine.step", "DecodeEngine.admit")

_REG = get_registry()
_M_PREFILL = _REG.histogram(
    "zoo_generation_prefill_duration_seconds",
    "Prefill wall time per admitted request, by prompt bucket",
    labelnames=("bucket",))
_M_STEP = _REG.histogram(
    "zoo_generation_decode_step_duration_seconds",
    "One fixed-shape decode step over the slot table (all active "
    "slots advance one token)")
_M_OCC = _REG.gauge(
    "zoo_generation_slot_occupancy_items",
    "Active decode slots (streams currently in the running batch)")
_M_KV = _REG.gauge(
    "zoo_generation_kv_utilization_ratio",
    "Assigned KV-cache pages / total pages (PagedKVCache accounting)")


def prefill_ladder(page_size: int, max_len: int) -> List[int]:
    """The prompt-length shape ladder: ``page_size`` doubling until it
    covers ``max_len``. Page-aligned by construction, so every bucket
    scatters into whole pages; the top entry is the positional-table
    size prefill can index."""
    out = [int(page_size)]
    while out[-1] < max_len:
        out.append(out[-1] * 2)
    return out


class DecodeEngine:
    """Slot-table decode over a paged KV pool.

    Args:
      model: a :class:`TinyGenLM` (or anything exposing its
        ``config``/``init_params``/``prefill``/``decode_step``
        surface).
      params: model parameter pytree; None = ``model.init_params()``
        (seeded -- the test/bench path).
      num_slots / page_size / num_pages / max_len: cache geometry;
        None reads the ``zoo.generation.*`` keys.

    Host API (all called from ONE worker loop thread):
      ``admit(prompt, max_new_tokens) -> (slot, first_token)``,
      ``step() -> [(slot, token), ...]``, ``release(slot)``,
      ``warm_up()``.
    """

    def __init__(self, model: TinyGenLM,
                 params: Optional[Dict[str, Any]] = None,
                 num_slots: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 max_len: Optional[int] = None,
                 dtype: Any = None):
        from analytics_zoo_tpu.common.config import get_config

        cfg = get_config()
        if num_slots is None:
            num_slots = int(cfg.get("zoo.generation.slots", 8))
        if page_size is None:
            page_size = int(cfg.get("zoo.generation.page_size", 16))
        if num_pages is None:
            num_pages = int(cfg.get("zoo.generation.num_pages", 0))
        if max_len is None:
            max_len = int(cfg.get("zoo.generation.max_len", 256))
        self.model = model
        c = model.config
        self.ladder = prefill_ladder(page_size, max_len)
        self.params = (params if params is not None
                       else model.init_params(pos_len=self.ladder[-1]))
        self.cache = PagedKVCache(
            num_layers=c.layers, num_heads=c.heads,
            head_dim=c.head_dim, page_size=page_size,
            num_slots=num_slots, num_pages=num_pages, max_len=max_len,
            dtype=dtype)
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        # per-slot decode state: the token the next step consumes and
        # the position it writes at (position L for a length-L prefix)
        self._tokens = np.zeros(self.num_slots, np.int32)
        self._positions = np.zeros(self.num_slots, np.int32)
        self._active: set = set()
        self._compiled_prefill: set = set()
        self._step_compiled = False
        import jax

        # donate the pool: both fns functionally rebuild the ENTIRE
        # kv array and the caller unconditionally replaces
        # self.cache.kv with the result, so without donation XLA must
        # keep the input alive -- one full-pool copy per generated
        # token and 2x peak HBM on the dominant allocation. (On CPU
        # donation is ignored with a one-time warning; the estimator's
        # train step donates its state the same way.)
        self._prefill_jit = jax.jit(self._prefill_impl,
                                    donate_argnums=(1,))
        self._step_jit = jax.jit(self._step_impl, donate_argnums=(1,))

    # ------------------------------------------------- jitted bodies --
    def _prefill_impl(self, params, kv, tokens, pages, last_idx):
        """Full forward over one padded prompt [Lb]; scatters its K/V
        pages into the pool (bucket pages beyond the prompt's
        assignment point at the trash page) and returns the greedy
        first token from the true last position."""
        import jax.numpy as jnp

        logits, k, v = self.model.prefill(params, tokens[None])
        npages = tokens.shape[0] // self.page_size
        c = self.model.config
        kc = k[:, 0].reshape(c.layers, npages, self.page_size,
                             c.heads, c.head_dim)
        vc = v[:, 0].reshape(c.layers, npages, self.page_size,
                             c.heads, c.head_dim)
        kv = kv.at[:, 0, pages].set(kc.astype(kv.dtype))
        kv = kv.at[:, 1, pages].set(vc.astype(kv.dtype))
        return kv, jnp.argmax(logits[0, last_idx]).astype(jnp.int32)

    def _step_impl(self, params, kv, tokens, positions, block):
        """One token for every slot lane (inactive lanes write to the
        trash page and produce ignored garbage -- fixed shape is the
        contract). Returns (kv', greedy tokens [S])."""
        import jax.numpy as jnp

        page = self.page_size
        t_ctx = block.shape[1] * page
        pp = jnp.take_along_axis(
            block, (positions // page)[:, None], axis=1)[:, 0]
        off = positions % page
        kvh = [kv]

        def write_kv(layer, k, v):
            kvh[0] = kvh[0].at[layer, 0, pp, off].set(
                k.astype(kv.dtype))
            kvh[0] = kvh[0].at[layer, 1, pp, off].set(
                v.astype(kv.dtype))

        def gather_kv(layer):
            bk = kvh[0][layer, 0][block].reshape(
                self.num_slots, t_ctx, -1, self.model.config.head_dim)
            bv = kvh[0][layer, 1][block].reshape(
                self.num_slots, t_ctx, -1, self.model.config.head_dim)
            mask = (jnp.arange(t_ctx)[None, :]
                    <= positions[:, None])
            return bk.astype(jnp.float32), bv.astype(jnp.float32), mask

        logits = self.model.decode_step(params, tokens, positions,
                                        gather_kv, write_kv)
        return kvh[0], jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # --------------------------------------------------------- admit --
    def can_admit(self, prompt_len: int, max_new_tokens: int) -> bool:
        return self.cache.can_admit(int(prompt_len)
                                    + int(max_new_tokens))

    def free_slots(self) -> int:
        return self.cache.free_slot_count()

    def active_slots(self) -> int:
        return len(self._active)

    def admit(self, prompt, max_new_tokens: int) -> Tuple[int, int]:
        """Join the running batch: claim a slot + pages, prefill the
        prompt into the pool, return ``(slot, first_token)``. Raises
        :class:`CacheOverflow` (the caller maps it to the structured
        ``generation_overflow`` refusal) and ValueError on an empty or
        over-long prompt. On success the CALLER owns the slot and owes
        :meth:`release` on every path (zoolint ``leak-on-path``
        enforces the pairing statically); on any failure past the
        claim, the slot is given back here before re-raising."""
        import jax.numpy as jnp

        prompt = np.asarray(prompt, np.int32).reshape(-1)
        lp = int(prompt.shape[0])
        if lp < 1:
            raise ValueError("empty prompt")
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        vocab = self.model.config.vocab
        if prompt.min() < 0 or prompt.max() >= vocab:
            raise ValueError(
                f"prompt token ids must be in [0, {vocab})")
        slot = self.cache.admit(lp, max_new_tokens)  # CacheOverflow
        try:
            return slot, self._prefill_slot(slot, prompt, lp)
        except BaseException:
            # anything after the claim (page assignment, prefill) must
            # give the slot + reservation back, or a poisoned request
            # permanently shrinks capacity (8 bad requests = a dead
            # engine)
            self.cache.release(slot)
            raise

    def _prefill_slot(self, slot: int, prompt: np.ndarray,
                      lp: int) -> int:
        import jax.numpy as jnp

        self.cache.ensure_length(slot, lp)
        bucket = next(b for b in self.ladder if b >= lp)
        padded = np.zeros(bucket, np.int32)
        padded[:lp] = prompt
        npages = bucket // self.page_size
        pages = np.zeros(npages, np.int32)  # trash beyond the prompt
        n_assigned = self.cache.pages_for(lp)
        pages[:n_assigned] = self.cache.block_tables()[
            slot, :n_assigned]
        fresh = bucket not in self._compiled_prefill
        t0 = time.perf_counter()
        kv, tok0 = self._prefill_jit(
            self.params, self.cache.kv, jnp.asarray(padded),
            jnp.asarray(pages), np.int32(lp - 1))
        tok0 = self._finalize_prefill(kv, tok0)
        wall = time.perf_counter() - t0
        if fresh:
            self._compiled_prefill.add(bucket)
            record_compile("generation.prefill",
                           [((bucket,), "int32")], wall,
                           subsystem="generation")
        _M_PREFILL.labels(bucket=str(bucket)).observe(wall)
        self._tokens[slot] = tok0
        self._positions[slot] = lp
        self._active.add(slot)
        self._update_gauges()
        return tok0

    def _finalize_prefill(self, kv, tok0) -> int:
        """Commit the new pool and sync the first token (the one host
        round-trip an admission pays)."""
        self.cache.kv = kv
        return int(np.asarray(tok0))

    # ---------------------------------------------------------- step --
    def step(self) -> List[Tuple[int, int]]:
        """Advance every active slot one token; returns
        ``[(slot, next_token), ...]`` for active slots only (the token
        each slot's *current* input produced). Empty batch = no-op."""
        import jax.numpy as jnp

        if not self._active:
            return []
        for slot in self._active:
            # lazy page assignment at the boundary (never fails inside
            # the admission-time reservation)
            self.cache.ensure_length(slot,
                                     int(self._positions[slot]) + 1)
        fresh = not self._step_compiled
        t0 = time.perf_counter()
        kv, toks = self._step_jit(
            self.params, self.cache.kv, jnp.asarray(self._tokens),
            jnp.asarray(self._positions),
            jnp.asarray(self.cache.block_tables()))
        out = self._finalize_step(kv, toks)
        wall = time.perf_counter() - t0
        if fresh:
            self._step_compiled = True
            record_compile(
                "generation.decode_step",
                [((self.num_slots,), "int32")], wall,
                subsystem="generation")
        _M_STEP.observe(wall)
        results = []
        for slot in sorted(self._active):
            nxt = int(out[slot])
            self._positions[slot] += 1
            self._tokens[slot] = nxt
            results.append((slot, nxt))
        return results

    def _finalize_step(self, kv, toks) -> np.ndarray:
        """Commit the pool and sync the step's S tokens to the host --
        the per-step device->host barrier (everything before it is
        async dispatch)."""
        self.cache.kv = kv
        return np.asarray(toks)

    # ------------------------------------------------------- release --
    def release(self, slot: int) -> None:
        """Leave the running batch: free the slot and its pages (block
        reuse -- the next admission takes them over)."""
        self._active.discard(slot)
        self._tokens[slot] = 0
        self._positions[slot] = 0
        self.cache.release(slot)
        self._update_gauges()

    # ------------------------------------------------------- handoff --
    # ISSUE-20: prefill/decode disaggregation. A prefill engine
    # exports a slot's full decode state -- page-aligned KV snapshot
    # plus the host slot registers (next input token, write position)
    # -- and a decode engine on another replica imports it and keeps
    # stepping bit-identically. Sampling is greedy argmax, so the slot
    # carries no sampler RNG; ``rng`` stays in the snapshot as an
    # explicit None so a future stochastic sampler extends the format
    # instead of forking it (replay determinism is the exactly-once
    # contract's foundation).

    def export_slot(self, slot: int) -> Dict[str, Any]:
        """Serialize an active slot for handoff. The slot stays active
        here -- the caller releases it once the handoff is safely
        published (or keeps decoding if publication failed)."""
        if slot not in self._active:
            raise ValueError(f"slot {slot} is not active")
        snap = self.cache.export_pages(slot)
        snap["next_token"] = int(self._tokens[slot])
        snap["position"] = int(self._positions[slot])
        snap["rng"] = None  # greedy decode: no sampler state
        return snap

    def import_slot(self, snapshot: Dict[str, Any]) -> int:
        """Re-admit a handed-off stream: claims a slot via
        :meth:`PagedKVCache.import_pages` (raising
        :class:`CacheOverflow` on exhaustion -- the caller maps it to
        the structured ``generation_overflow`` refusal), restores the
        slot registers, and joins the running batch. On success the
        CALLER owns the slot and owes :meth:`release` on every path,
        exactly as for :meth:`admit`."""
        slot = self.cache.import_pages(snapshot)  # CacheOverflow
        try:
            self._tokens[slot] = int(snapshot["next_token"])
            self._positions[slot] = int(snapshot["position"])
            self._active.add(slot)
            self._update_gauges()
        except BaseException:
            # a malformed register (non-int next_token) must not
            # strand the pages import_pages just claimed
            self.cache.release(slot)
            self._active.discard(slot)
            raise
        return slot

    def _update_gauges(self) -> None:
        _M_OCC.set(len(self._active))
        _M_KV.set(self.cache.utilization())

    # ------------------------------------------------------- warm-up --
    def warm_up(self) -> "DecodeEngine":
        """Compile the whole prefill ladder and the decode step before
        traffic arrives, flagged warm so N shapes in N seconds don't
        read as a recompile storm. Writes land on the trash page; slot
        state and accounting are untouched."""
        import jax.numpy as jnp

        with warming():
            for bucket in self.ladder:
                if bucket in self._compiled_prefill:
                    continue
                t0 = time.perf_counter()
                kv, _ = self._prefill_jit(
                    self.params, self.cache.kv,
                    jnp.zeros(bucket, jnp.int32),
                    jnp.zeros(bucket // self.page_size, jnp.int32),
                    np.int32(0))
                self.cache.kv = kv
                self._compiled_prefill.add(bucket)
                record_compile("generation.prefill",
                               [((bucket,), "int32")],
                               time.perf_counter() - t0,
                               subsystem="generation", warm=True)
            if not self._step_compiled:
                t0 = time.perf_counter()
                kv, _ = self._step_jit(
                    self.params, self.cache.kv,
                    jnp.zeros(self.num_slots, jnp.int32),
                    jnp.zeros(self.num_slots, jnp.int32),
                    jnp.asarray(self.cache.block_tables()))
                self.cache.kv = kv
                self._step_compiled = True
                record_compile("generation.decode_step",
                               [((self.num_slots,), "int32")],
                               time.perf_counter() - t0,
                               subsystem="generation", warm=True)
        return self

    # --------------------------------------------------------- stats --
    def stats(self) -> Dict[str, Any]:
        return {
            "slots": self.num_slots,
            "active": len(self._active),
            "ladder": list(self.ladder),
            "prefill_buckets_compiled": sorted(self._compiled_prefill),
            "cache": self.cache.stats(),
        }


def engine_from_config(gen_cfg: Dict[str, Any]) -> DecodeEngine:
    """Build an engine from a launcher ``generation:`` YAML block:
    ``model:`` holds :class:`GenModelConfig` fields (the seeded
    builtin LM); ``slots``/``page_size``/``num_pages``/``max_len``
    override the ``zoo.generation.*`` defaults for this launch only."""
    model_cfg = dict(gen_cfg.get("model") or {})
    config = GenModelConfig.from_dict(model_cfg)
    return DecodeEngine(
        TinyGenLM(config),
        num_slots=gen_cfg.get("slots"),
        page_size=gen_cfg.get("page_size"),
        num_pages=gen_cfg.get("num_pages"),
        max_len=gen_cfg.get("max_len"))
