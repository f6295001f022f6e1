"""Seq2seq encoder-decoder model.

The analog of ``Seq2seq`` (ref: zoo/.../models/seq2seq/Seq2seq.scala --
RNNEncoder/RNNDecoder/Bridge; pyzoo/zoo/models/seq2seq): stacked-LSTM
encoder, state bridge (direct pass or dense projection), stacked-LSTM
decoder with teacher forcing for training and greedy ``infer`` for
generation. Token-id sequences; id 0 is padding.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.models.common import ZooModel, register_model


class Seq2seqNet(nn.Module):
    vocab: int
    embed_dim: int
    hidden_sizes: Tuple[int, ...]
    bridge: str = "pass"  # "pass" | "dense"

    @nn.compact
    def __call__(self, x):
        """Teacher-forced forward: {"src": [B, Ls], "tgt_in": [B, Lt]}
        -> logits [B, Lt, vocab+1]."""
        if isinstance(x, dict):
            src, tgt_in = x["src"], x["tgt_in"]
        else:
            src, tgt_in = x
        embed = nn.Embed(self.vocab + 1, self.embed_dim, name="embed")
        h = embed(src.astype(jnp.int32))
        states = []
        for i, hsz in enumerate(self.hidden_sizes):
            carry, h = nn.RNN(nn.OptimizedLSTMCell(hsz),
                              return_carry=True, name=f"enc_{i}")(h)
            states.append(carry)
        if self.bridge == "dense":
            states = [
                (jnp.tanh(nn.Dense(hsz, name=f"bridge_c_{i}")(c)),
                 jnp.tanh(nn.Dense(hsz, name=f"bridge_h_{i}")(hh)))
                for i, (hsz, (c, hh)) in enumerate(
                    zip(self.hidden_sizes, states))]
        d = embed(tgt_in.astype(jnp.int32))
        for i, hsz in enumerate(self.hidden_sizes):
            d = nn.RNN(nn.OptimizedLSTMCell(hsz), name=f"dec_{i}")(
                d, initial_carry=states[i])
        return nn.Dense(self.vocab + 1, name="head")(d)


@register_model
class Seq2seq(ZooModel):
    """(ref: Seq2seq.scala). Train on {"src", "tgt_in"} -> labels
    ``tgt_out`` (the target shifted by one)."""

    default_optimizer = "adam"

    @staticmethod
    def default_loss(preds, labels):
        """Padding-masked CE over the time dimension."""
        labels = jnp.asarray(labels).astype(jnp.int32)
        logp = jax.nn.log_softmax(preds, -1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = (labels > 0).astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    def __init__(self, vocab: int, embed_dim: int = 128,
                 hidden_sizes=(128,), bridge: str = "pass",
                 max_len: int = 32):
        super().__init__(vocab=vocab, embed_dim=embed_dim,
                         hidden_sizes=list(hidden_sizes), bridge=bridge,
                         max_len=max_len)

    def _build_module(self):
        c = self._config
        return Seq2seqNet(vocab=c["vocab"], embed_dim=c["embed_dim"],
                          hidden_sizes=tuple(c["hidden_sizes"]),
                          bridge=c["bridge"])

    def _example_input(self):
        return {"src": np.ones((1, 4), np.int32),
                "tgt_in": np.ones((1, 4), np.int32)}

    def infer(self, src, start_id: int, max_len: Optional[int] = None,
              host_loop: bool = False):
        """Greedy generation (ref: Seq2seq.scala infer).

        Default: the whole greedy loop runs on-device inside ONE
        jitted ``lax.fori_loop`` -- one dispatch per call instead of
        one per emitted token (the ISSUE-10 satellite fix: the old
        host loop paid ``max_len`` python->device round trips). One
        compile per (batch, max_len) shape, cached on the model.

        ``host_loop=True`` keeps the original per-token host loop --
        the parity reference of ``tests/test_generation.py`` and the
        escape hatch for duck-typed modules jit can't trace.
        """
        max_len = max_len or self._config["max_len"]
        src = np.asarray(src, np.int32)
        est = self.estimator
        est._ensure_built({"src": src[:1], "tgt_in": src[:1, :1]})
        module = self.module

        if host_loop:
            @jax.jit
            def step(variables, src, tgt_in):
                return module.apply(variables,
                                    {"src": src, "tgt_in": tgt_in})

            b = src.shape[0]
            tgt_in = np.zeros((b, max_len), np.int32)
            tgt_in[:, 0] = start_id
            out = np.zeros((b, max_len), np.int32)
            for t in range(max_len):
                logits = np.asarray(step(est.variables, src, tgt_in))
                tok = logits[:, t].argmax(-1).astype(np.int32)
                out[:, t] = tok
                if t + 1 < max_len:
                    tgt_in[:, t + 1] = tok
            return out

        fns = self.__dict__.setdefault("_infer_fns", {})
        gen = fns.get(max_len)
        if gen is None:
            def gen_impl(variables, src_dev, start):
                b = src_dev.shape[0]
                # buffer one column wider than the window so the
                # unconditional write at t+1 never needs a bounds
                # branch; the forward always sees buf[:, :max_len]
                buf0 = jnp.zeros((b, max_len + 1),
                                 jnp.int32).at[:, 0].set(start)
                out0 = jnp.zeros((b, max_len), jnp.int32)

                def body(t, carry):
                    buf, out = carry
                    logits = module.apply(
                        variables,
                        {"src": src_dev,
                         "tgt_in": jax.lax.slice_in_dim(
                             buf, 0, max_len, axis=1)})
                    tok = jnp.argmax(logits[:, t], -1).astype(
                        jnp.int32)
                    return (buf.at[:, t + 1].set(tok),
                            out.at[:, t].set(tok))

                _, out = jax.lax.fori_loop(0, max_len, body,
                                           (buf0, out0))
                return out

            gen = fns[max_len] = jax.jit(gen_impl)
        return np.asarray(gen(est.variables, src,
                              jnp.int32(start_id)))
