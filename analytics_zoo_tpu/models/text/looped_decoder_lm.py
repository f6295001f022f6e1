"""A looped (depth-recurrent, weight-shared) causal language model:
one stack of ``n_layers`` ``LoopedDecoderLayer`` blocks applied
``n_passes`` times with the SAME parameters, the final norm, the head
and an exit gate after every pass, trained on the exit-weighted loss
("Scaling Latent Reasoning via Looped Language Models", stage I):

    u = Embed[ids]
    for t = 1 .. T:
        u = layers(u);  h_t = RMSNorm_f(u);  u = h_t
        z_t = h_t W_head  (float32);  lambda_t = sigmoid(h_t w_g + b_g)
    S_0 = 1;  p_t = lambda_t S_{t-1},  S_t = S_{t-1} - p_t  (t < T);
    p_T = S_{T-1}
    loss = mean over tokens of  sum_t p_t CE(z_t, y)  -  beta H(p)

The parameter tree holds each layer once (``stack/layer_i``): the
passes are a ``scan`` whose body is the stack with the parameters
broadcast, so a shared weight's gradient is summed over the passes in
the backward loop's carry and the step program holds ``n_layers`` layer
bodies, not ``n_passes x n_layers``.

No ``[L, vocab]`` array exists in the train step. The heads never
produce logits there: ``blocked_logsumexp`` gives each pass's
``logsumexp(z_t)`` a row block at a time and computes the block's
logits again in its backward pass, and the label's own logit is one
product with the label's column of the head (``exit_weighted_loss``).
So what the module hands the loss in training is not logits but a
dict: every pass's ``h_t``, its logsumexp a token, the exit
distribution's logarithm and the head. ``predict`` returns ``z_T``, the last
pass's float32 logits (the cumulative exit mass reaches an
``early_exit_threshold`` of 1 only there).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.keras.layers.looped_decoder import LoopedDecoderLayer
from analytics_zoo_tpu.keras.layers.sparse_decoder import (
    MLP_OUT_NAME, RMSNorm)
from analytics_zoo_tpu.models.common import register_model
from analytics_zoo_tpu.models.text.sparse_decoder_lm import (
    _DecoderLM, _embed, _ids, _rematerialised, next_token_loss)

# What a layer application keeps for the backward pass beside its
# input. Activation memory scales with n_passes x n_layers here and
# parameter memory with n_layers, so the three other decoders'
# ``KEPT_NAMES`` (about 0.5 GB an application at [1, 8192, 2048]) do not
# fit 32 applications beside 9.8 GB of state; chosen by the memory
# reading (benchmark/configs/ouro-2.6b.json, ``assumed.rematerialisation``).
LOOP_KEPT_NAMES = (MLP_OUT_NAME,)
# rows of one block of the heads: [rows, vocab] float32 logits live at once
HEAD_BLOCK_ROWS = 1024
# what a step's mean (a pass's cross-entropy in nats, its exit
# probability) is multiplied by before it is added to its int32 counter
COUNT_SCALE = 1000


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def blocked_logsumexp(h, head, block: int, dtype):
    """``logsumexp(h @ head, -1)`` in float32, [rows] for ``h`` [rows,
    d] and ``head`` [d, vocab]: the products in ``dtype`` with float32
    sums, ``block`` rows at a time, so that [block, vocab] is the most
    of the logits that exists at once. The backward pass computes each
    block's logits again and sums the head's gradient in float32."""
    return _blocked_lse_fwd(h, head, block, dtype)[0]


def _blocks(a, block: int):
    return a.reshape((a.shape[0] // block, block) + a.shape[1:])


def _blocked_lse_fwd(h, head, block, dtype):
    if h.shape[0] % block:
        raise ValueError(f"{h.shape[0]} rows are no multiple of {block}")
    w = head.astype(dtype)

    def one(hb):
        return jax.nn.logsumexp(
            jnp.dot(hb, w, preferred_element_type=jnp.float32), axis=-1)

    lse = jax.lax.map(one, _blocks(h.astype(dtype), block)).reshape(-1)
    return lse, (h, head, lse)


def _blocked_lse_bwd(block, dtype, residuals, g):
    h, head, lse = residuals
    w = head.astype(dtype)

    def one(d_head, inputs):
        hb, lse_b, g_b = inputs
        z = jnp.dot(hb, w, preferred_element_type=jnp.float32)
        p = (jnp.exp(z - lse_b[:, None]) * g_b[:, None]).astype(dtype)
        d_hb = jnp.dot(p, w.T, preferred_element_type=jnp.float32)
        return d_head + jnp.dot(hb.T, p,
                                preferred_element_type=jnp.float32), d_hb

    d_head, d_h = jax.lax.scan(
        one, jnp.zeros(head.shape, jnp.float32),
        (_blocks(h.astype(dtype), block), _blocks(lse, block),
         _blocks(g.astype(jnp.float32), block)))
    return d_h.reshape(h.shape).astype(h.dtype), d_head.astype(head.dtype)


blocked_logsumexp.defvjp(_blocked_lse_fwd, _blocked_lse_bwd)


def head_logsumexp(h, head, dtype):
    """``blocked_logsumexp`` of [..., d] states in blocks of
    ``HEAD_BLOCK_ROWS``, the last block padded with rows of zeros."""
    rows = h.reshape(-1, h.shape[-1])
    n = rows.shape[0]
    block = min(HEAD_BLOCK_ROWS, n)
    rows = jnp.pad(rows, ((0, -n % block), (0, 0)))
    return blocked_logsumexp(rows, head, block, dtype)[:n].reshape(
        h.shape[:-1])


def exit_log_distribution(gate):
    """Log exit probabilities [T, ...] of the gates' float32
    pre-activations [T, ...]: ``p_t = sigmoid(gate_t) S_{t-1}`` with
    ``S_0 = 1``, ``S_t = S_{t-1} - p_t``; the last pass takes what is
    left. In logarithms (``log S_t`` is the sum of ``log sigmoid(-gate)``
    so far) so that a gate that has run to one end, where ``sigmoid``
    is exactly 0 or 1 in float32, leaves every ``log p_t`` and the
    loss's gradient finite."""
    log_survive = jnp.zeros(gate.shape[1:], jnp.float32)
    log_p = []
    for t in range(gate.shape[0] - 1):
        log_p.append(jax.nn.log_sigmoid(gate[t]) + log_survive)
        log_survive = log_survive + jax.nn.log_sigmoid(-gate[t])
    return jnp.stack(log_p + [log_survive])


def exit_distribution(gate):
    """Exit probabilities [T, ...]: ``exp(exit_log_distribution)``."""
    return jnp.exp(exit_log_distribution(gate))


def _picked(states, head, labels):
    """float32 [T, B, L]: each pass's logit of ``labels`` [B, L], from
    the labels' columns of the head."""
    columns = jnp.take(head.astype(states.dtype), labels, axis=1)  # [d, B, L]
    return jnp.einsum("tbld,dbl->tbl", states, columns,
                      preferred_element_type=jnp.float32)


def exit_weighted_loss(preds, labels, beta: float = 0.1):
    """The mean over tokens of ``sum_t p_t CE(z_t, y) - beta H(p)`` from
    what ``LoopedDecoderModule`` returns in training (``states`` [T, B,
    L, d], ``lse`` and ``log_exit`` [T, B, L] float32, ``head`` [d, V];
    ``H(p) = - sum_t p_t log p_t`` from the logarithms themselves).
    Given an array -- ``z_T`` [B, L, V], what the module returns
    outside training -- the last pass's ``next_token_loss``."""
    if not isinstance(preds, dict):
        return next_token_loss(preds, labels)
    with jax.named_scope("exit_loss"):
        log_p = preds["log_exit"]
        nll = preds["lse"] - _picked(preds["states"], preds["head"],
                                     labels.astype(jnp.int32))
        return jnp.mean(jnp.sum(jnp.exp(log_p) * (nll + beta * log_p),
                                axis=0))


class _LoopedStack(nn.Module):
    """One pass: the layers, then the final norm. The body of the scan
    over the passes (carry in, carry and ``h_t`` out)."""

    n_layers: int
    n_head: int
    head_dim: int
    dense_width: int
    rope_theta: float
    eps: float
    init_std: float
    train: bool
    dtype: Any

    @nn.compact
    def __call__(self, u, _):
        with jax.named_scope("loop_body"):
            layer = _rematerialised(LoopedDecoderLayer, LOOP_KEPT_NAMES)
            for i in range(self.n_layers):
                u = layer(
                    n_head=self.n_head, head_dim=self.head_dim,
                    dense_width=self.dense_width,
                    rope_theta=self.rope_theta, eps=self.eps,
                    init_std=self.init_std, dtype=self.dtype,
                    name=f"layer_{i}")(u, self.train)
            # rematerialised too: the scan would keep its float32
            # intermediates for every pass (0.77 GB at [4, 1, 8192, 2048])
            h = nn.remat(RMSNorm)(self.eps, self.dtype, name="final_norm")(u)
        return h, h


class LoopedDecoderModule(nn.Module):
    vocab: int
    hidden_size: int
    n_layers: int
    n_passes: int
    n_head: int
    head_dim: int
    dense_width: int
    rope_theta: float = 10000.0
    eps: float = 1e-6
    init_std: float = 0.02
    scale_embedding: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        u = _embed(self, x, self.init_std)
        stack = nn.scan(
            _LoopedStack, variable_broadcast="params",
            split_rngs={"params": False}, length=self.n_passes)(
            n_layers=self.n_layers, n_head=self.n_head,
            head_dim=self.head_dim, dense_width=self.dense_width,
            rope_theta=self.rope_theta, eps=self.eps,
            init_std=self.init_std, train=train, dtype=self.dtype,
            name="stack")
        _, states = stack(u, None)                       # [T, B, L, d]
        head = self.param("head", nn.initializers.normal(self.init_std),
                          (self.hidden_size, self.vocab))
        if not (train or self.is_initializing()):
            return jnp.dot(states[-1], head.astype(self.dtype),
                           preferred_element_type=jnp.float32)
        with jax.named_scope("loop_head"):
            w_g = self.param("exit_gate_kernel",
                             nn.initializers.normal(self.init_std),
                             (self.hidden_size,))
            b_g = self.param("exit_gate_bias", nn.initializers.zeros, (1,))
            gate = jnp.dot(states, w_g.astype(self.dtype),
                           preferred_element_type=jnp.float32) + b_g
            log_exit = exit_log_distribution(gate)
            lse = head_logsumexp(states, head, self.dtype)
            self._count(states, head, lse, jnp.exp(log_exit), _ids(x), train)
        return {"states": states, "lse": lse, "log_exit": log_exit,
                "head": head}

    def _count(self, states, head, lse, exits, ids, train: bool):
        """Collection ``counters`` (cumulative int32, published by the
        Estimator at each epoch's sync as ``zoo_model_loop_*_total``):
        the steps counted, each pass's mean cross-entropy of the step in
        thousandths of a nat over the targets that lie in the row's own
        input (``ids[t + 1]``), and each pass's mean exit probability in
        thousandths. Their growths' ratios are an epoch's means.

        The Estimator takes a counter's growth modulo 2**32, so one
        epoch may add less than that: at ``COUNT_SCALE`` a step adds at
        most 1,000 to an exit probability and ``1e3 ln(vocab)`` to a
        loss at its start (10,803 at 49,152 ids), which is right for
        epochs of up to 4.2 million and about 390,000 steps. (Millionths
        would wrap the loss in 400 steps.)"""
        counting = train and self.is_mutable_collection("counters")
        if not (counting or self.is_initializing()):
            return
        t = self.n_passes
        adds = {"loop_steps": jnp.ones((), jnp.int32),
                "loop_pass_loss_millinats": jnp.zeros((t,), jnp.int32),
                "loop_exit_probability_thousandths": jnp.zeros(
                    (t,), jnp.int32)}
        if counting:
            states, head, lse, exits = jax.lax.stop_gradient(
                (states, head, lse, exits))
            nll = lse[:, :, :-1] - _picked(states[:, :, :-1], head,
                                           ids[:, 1:])
            adds["loop_pass_loss_millinats"] = jnp.round(
                COUNT_SCALE * jnp.mean(nll, (1, 2))).astype(jnp.int32)
            adds["loop_exit_probability_thousandths"] = jnp.round(
                COUNT_SCALE * jnp.mean(exits, (1, 2))).astype(jnp.int32)
        for name, add in adds.items():
            counter = self.variable(
                "counters", name,
                lambda a=add: jnp.zeros(a.shape, jnp.int32))
            if counting:
                counter.value = counter.value + add


@register_model
class LoopedDecoderLM(_DecoderLM):
    """``n_layers`` ``LoopedDecoderLayer`` blocks applied ``n_passes``
    times with one set of parameters, a head and an exit gate after
    every pass; ``_DecoderLM``'s contract (predict returns the last
    pass's float32 logits [B, L, vocab]). The default loss is
    ``exit_weighted_loss`` at ``beta``."""

    def __init__(self, vocab: int, hidden_size: int, n_layers: int,
                 n_passes: int, n_head: int, head_dim: int,
                 dense_width: int, beta: float = 0.1,
                 rope_theta: float = 10000.0, eps: float = 1e-6,
                 init_std: float = 0.02, scale_embedding: bool = False,
                 dtype: str = "float32"):
        self.default_loss = functools.partial(exit_weighted_loss, beta=beta)
        super().__init__(
            vocab=vocab, hidden_size=hidden_size, n_layers=n_layers,
            n_passes=n_passes, n_head=n_head, head_dim=head_dim,
            dense_width=dense_width, beta=beta, rope_theta=rope_theta,
            eps=eps, init_std=init_std, scale_embedding=scale_embedding,
            dtype=dtype)

    def _build_module(self):
        c = dict(self._config)
        c.pop("beta")
        c["dtype"] = jnp.dtype(c["dtype"])
        return LoopedDecoderModule(**c)
