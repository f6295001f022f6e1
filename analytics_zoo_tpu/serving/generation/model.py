"""Causal-transformer LM with explicit prefill / decode-step math.

The generation engine needs a model whose forward splits the way the
serving path splits: a *prefill* over the whole prompt (compute-bound,
bucketed on prompt length, rides the causal attention dispatch in
``ops/`` -- the Pallas flash kernel on TPU when shapes allow) and a
*decode step* for one position per slot against the paged KV pool
(memory-bound, fixed shape). Flax's module system hides exactly the
seam we need, so the parameters here are a plain pytree and the two
phases are plain functions the engine jits.

:class:`TinyGenLM` is deliberately small and deterministic (seeded
init): it is the reference generation model of the test suite and the
perf driver, the role ``_TinyNet`` plays for the predict path. Real
checkpoints plug in by implementing the same three functions over
their own params (``docs/serving.md`` "Generation serving").

Pre-LN transformer block; learned positional embeddings; all f32 so
greedy argmax parity between the prefill path, the paged decode step,
and the re-run-the-whole-prefix reference is a float-noise question
with margins, not a dtype question.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class GenModelConfig:
    """Geometry of a :class:`TinyGenLM` (and of the KV pool serving
    it -- the engine reads layers/heads/head_dim from here)."""

    vocab: int = 64
    dim: int = 32
    heads: int = 2
    head_dim: int = 16
    layers: int = 2
    max_len: int = 256
    mlp_ratio: int = 2
    seed: int = 0

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "GenModelConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(
                f"unknown generation model fields: {sorted(unknown)} "
                f"(known: {sorted(known)})")
        return cls(**{k: int(v) for k, v in d.items()})


def _ln(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale + bias


class TinyGenLM:
    """Seeded parameter factory + the prefill / decode-step forwards.

    All methods are pure functions of ``(params, inputs)`` -- the
    engine owns jit/caching; instances only carry the config.
    """

    def __init__(self, config: GenModelConfig):
        self.config = config

    # ------------------------------------------------------- params --
    def init_params(self, pos_len: int | None = None) -> Dict[str, Any]:
        """Deterministic f32 parameter pytree. ``pos_len`` sizes the
        positional table (the engine passes its prefill-ladder top so
        padded prefill buckets never index past it)."""
        c = self.config
        pos_len = int(pos_len or c.max_len)
        rng = np.random.RandomState(c.seed)

        def mat(*shape, scale=None):
            scale = scale if scale is not None else 1.0 / np.sqrt(
                shape[0])
            return jnp.asarray(
                rng.normal(0.0, scale, shape).astype(np.float32))

        inner = c.heads * c.head_dim
        blocks = []
        for _ in range(c.layers):
            blocks.append({
                "ln1_s": jnp.ones((c.dim,), jnp.float32),
                "ln1_b": jnp.zeros((c.dim,), jnp.float32),
                "wq": mat(c.dim, inner), "wk": mat(c.dim, inner),
                "wv": mat(c.dim, inner), "wo": mat(inner, c.dim),
                "ln2_s": jnp.ones((c.dim,), jnp.float32),
                "ln2_b": jnp.zeros((c.dim,), jnp.float32),
                "w1": mat(c.dim, c.dim * c.mlp_ratio),
                "w2": mat(c.dim * c.mlp_ratio, c.dim),
            })
        return {
            # deliberately hot init (unit-scale embeddings + strong
            # positional signal): a near-zero random LM's greedy
            # trajectory collapses to one repeated argmax within a
            # couple of tokens, which would let cross-slot
            # contamination bugs hide behind identical fixed points in
            # the parity tests; position-dependent dynamics keep
            # trajectories distinct per (prompt, position)
            "embed": mat(c.vocab, c.dim, scale=1.0),
            "pos": mat(pos_len, c.dim, scale=1.0),
            "blocks": blocks,
            "lnf_s": jnp.ones((c.dim,), jnp.float32),
            "lnf_b": jnp.zeros((c.dim,), jnp.float32),
            "head": mat(c.dim, c.vocab, scale=1.0),
        }

    # ------------------------------------------------------ prefill --
    def prefill(self, params, tokens) -> Tuple[Any, Any, Any]:
        """Full causal forward over ``tokens`` [B, L].

        Returns ``(logits [B, L, vocab], k, v)`` with k/v stacked
        [layers, B, L, heads, head_dim] -- the cache chunks the engine
        scatters into the page pool. Attention routes through the ops
        dispatcher, so TPU prefill rides the owned causal Pallas flash
        kernel when shapes allow (``ops.attention.attention_path``)."""
        from analytics_zoo_tpu.ops.attention import (
            dot_product_attention)

        c = self.config
        b, l = tokens.shape
        x = params["embed"][tokens] + params["pos"][:l][None]
        ks, vs = [], []
        for blk in params["blocks"]:
            h = _ln(x, blk["ln1_s"], blk["ln1_b"])
            q = (h @ blk["wq"]).reshape(b, l, c.heads, c.head_dim)
            k = (h @ blk["wk"]).reshape(b, l, c.heads, c.head_dim)
            v = (h @ blk["wv"]).reshape(b, l, c.heads, c.head_dim)
            o = dot_product_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=True)
            x = x + o.transpose(0, 2, 1, 3).reshape(
                b, l, c.heads * c.head_dim) @ blk["wo"]
            h2 = _ln(x, blk["ln2_s"], blk["ln2_b"])
            x = x + jax.nn.relu(h2 @ blk["w1"]) @ blk["w2"]
            ks.append(k)
            vs.append(v)
        logits = _ln(x, params["lnf_s"], params["lnf_b"]) @ params["head"]
        return logits, jnp.stack(ks), jnp.stack(vs)

    # -------------------------------------------------- decode step --
    def decode_step(self, params, tokens, positions, gather_kv,
                    write_kv):
        """One position per slot: ``tokens``/``positions`` are [S].

        The cache is abstracted behind two callbacks so this math stays
        pool-layout-agnostic: ``write_kv(layer, k, v)`` commits this
        position's [S, H, D] k/v, ``gather_kv(layer)`` returns the
        slot-table context ``(K, V)`` as [S, T, H, D] plus the
        attendable-position mask [S, T]. Returns logits [S, vocab]."""
        c = self.config
        x = params["embed"][tokens] + params["pos"][positions]
        for li, blk in enumerate(params["blocks"]):
            h = _ln(x, blk["ln1_s"], blk["ln1_b"])
            q = (h @ blk["wq"]).reshape(-1, c.heads, c.head_dim)
            k = (h @ blk["wk"]).reshape(-1, c.heads, c.head_dim)
            v = (h @ blk["wv"]).reshape(-1, c.heads, c.head_dim)
            write_kv(li, k, v)
            bk, bv, mask = gather_kv(li)
            scores = jnp.einsum(
                "shd,sthd->sht", q, bk,
                preferred_element_type=jnp.float32)
            scores = scores / np.sqrt(c.head_dim)
            scores = jnp.where(mask[:, None, :], scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("sht,sthd->shd", probs.astype(bv.dtype), bv)
            x = x + o.reshape(-1, c.heads * c.head_dim) @ blk["wo"]
            h2 = _ln(x, blk["ln2_s"], blk["ln2_b"])
            x = x + jax.nn.relu(h2 @ blk["w1"]) @ blk["w2"]
        return _ln(x, params["lnf_s"], params["lnf_b"]) @ params["head"]

    # ---------------------------------------------------- reference --
    def reference_generate(self, params, prompt, max_new_tokens: int,
                           eos: int = -1) -> np.ndarray:
        """Greedy generation by re-running the full prefill on the
        growing prefix every token -- the unbatched, cache-free
        reference the engine's paged decode is parity-tested against
        (and the naive baseline of the perf A/B). One jit compile per
        prefix length; O(T^2) device calls by construction."""
        toks = list(np.asarray(prompt, np.int32).reshape(-1))
        out = []
        for _ in range(int(max_new_tokens)):
            arr = jnp.asarray(np.asarray(toks, np.int32)[None])
            logits, _, _ = self.prefill(params, arr)
            nxt = int(np.asarray(jnp.argmax(logits[0, -1])))
            out.append(nxt)
            toks.append(nxt)
            if eos >= 0 and nxt == eos:
                break
        return np.asarray(out, np.int32)
