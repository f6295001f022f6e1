"""Plain reference: the Trinity-Mini (``afmoe``) decoder, forward pass,
next-token loss and gradients in float32 ``jax.numpy`` with
``default_matmul_precision("highest")``. No kernel, no sort, no cache:
attention materialises its scores for a block of query rows at a time
(so that L = 8192 fits), and the expert layer loops over the experts
held, each on every token, weighted by what the router gave it.

Per layer, kind ``sliding_attention`` or ``full_attention``
(``benchmark/configs/trinity-mini.json`` lists what no key of the
published config carries, under ``assumed``):

    a = RMSNorm(h);  q, k, v, g = a Wq, a Wk, a Wv, a Wg
    q, k = RMSNorm(q), RMSNorm(k) per head;  RoPE on sliding layers only
    s_ij = q_i k_j / sqrt(head_dim), j <= i, sliding: i - j < window;
    query head n reads KV head n // (heads / kv_heads)
    h = h + RMSNorm((softmax(s) v * sigmoid(g)) Wo)
    m = RMSNorm(h)
    dense layer:  f = (silu(m W1) * (m W3)) W2
    expert layer: r = sigmoid(m Wr); S = top_k(r + b);
                  w_e = r_e / (sum_S r + 1e-20) * route_scale
                  f = SwiGLU_shared(m) + sum_{e in S, e held} w_e SwiGLU_e(m)
    h = h + RMSNorm(f)
    logits = RMSNorm(h) W_head,  h0 = Embed[ids] * sqrt(d)

It is given the same share as the program: the experts
``first_expert_held .. + num_experts`` of ``num_experts_routed_over``
and ``vocab_size`` rows of the vocabulary. What absent experts would
add is left out. Nothing is shared with the program's model code; only
:func:`weights_from_program` knows the program's parameter names.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

QUERY_ROWS = 512        # attention scores exist for this many rows at once
_ROUND_OPERANDS_TO = None


@contextlib.contextmanager
def operands_rounded_to(dtype):
    """Inside, every matrix product's operands are rounded to ``dtype``
    first (the products themselves stay float32). For the one reading
    that sets the cell's tolerance from below: this reference in the
    next precision under the configuration's (``float8_e4m3fn`` under
    bfloat16) must come out as NOT correct."""
    global _ROUND_OPERANDS_TO
    before, _ROUND_OPERANDS_TO = _ROUND_OPERANDS_TO, dtype
    try:
        yield
    finally:
        _ROUND_OPERANDS_TO = before


def _rounded(a):
    if _ROUND_OPERANDS_TO is None:
        return a
    return a.astype(_ROUND_OPERANDS_TO).astype(jnp.float32)


def _mm(a, b):
    return _rounded(a) @ _rounded(b)


def weights_from_program(variables: dict) -> dict:
    """The program's ``SparseDecoderModule`` tree -> neutral names.
    Traceable, so gradients come back in the program's own tree."""
    p = variables["params"]
    state = variables.get("router_state", {})

    def f32(a):
        return jnp.asarray(a, jnp.float32)

    def swiglu(d):
        return tuple(f32(d[k]["kernel"]) for k in ("w1", "w3", "w2"))

    layers = []
    while f"layer_{len(layers)}" in p:
        name = f"layer_{len(layers)}"
        lp, a = p[name], p[name]["attention"]
        layer = {
            "norms": {k: f32(lp[k]["scale"]) for k in (
                "input_norm", "post_attention_norm", "pre_mlp_norm",
                "post_mlp_norm")},
            "wq": f32(a["q"]["kernel"]), "wk": f32(a["k"]["kernel"]),
            "wv": f32(a["v"]["kernel"]), "wg": f32(a["gate"]["kernel"]),
            "wo": f32(a["out"]["kernel"]),
            "q_norm": f32(a["q_norm"]["scale"]),
            "k_norm": f32(a["k_norm"]["scale"]),
        }
        if "moe" in lp:
            moe = lp["moe"]
            layer["moe"] = {
                "router": f32(moe["router"]["kernel"]),
                "bias": f32(state[name]["moe"]["bias"]),
                "experts": tuple(f32(moe[k]) for k in ("w1", "w3", "w2")),
                "shared": swiglu(moe["shared"]) if "shared" in moe else None,
            }
        else:
            layer["mlp"] = swiglu(lp["mlp"])
        layers.append(layer)
    return {"embed": f32(p["embed"]["embedding"]), "layers": layers,
            "final_norm": f32(p["final_norm"]["scale"]),
            "head": f32(p["head"])}


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """[L, heads, D], rotate-half, positions 0..L-1."""
    l, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(l, dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], -1)[:, None]       # [L, 1, D]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)


def attention(q, k, v, window):
    """q [L, H, D], k, v [L, H_kv, D] -> [L, H, D]; causal, and with
    ``window`` only the ``window`` newest keys."""
    l, h, d = q.shape
    group = h // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    out = []
    for start in range(0, l, QUERY_ROWS):
        rows = jnp.arange(start, min(start + QUERY_ROWS, l))[:, None]
        keys = jnp.arange(l)[None]
        keep = keys <= rows
        if window is not None:
            keep &= rows - keys < window
        s = jnp.einsum("qhd,khd->hqk",
                       _rounded(q[start:start + QUERY_ROWS]), _rounded(k))
        s = jnp.where(keep[None], s / jnp.sqrt(float(d)), -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd",
                              _rounded(jax.nn.softmax(s, -1)), _rounded(v)))
    return jnp.concatenate(out, 0)


def swiglu(m, weights):
    w1, w3, w2 = weights
    return _mm(jax.nn.silu(_mm(m, w1)) * _mm(m, w3), w2)


def route(m, moe, config):
    """Weights [n, k] and expert ids [n, k] over all routed experts."""
    r = jax.nn.sigmoid(m @ moe["router"])       # float32 at any setting
    _, chosen = jax.lax.top_k(r + moe["bias"], config["num_experts_per_tok"])
    picked = jnp.take_along_axis(r, chosen, -1)
    if config["route_norm"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return picked * config["route_scale"], chosen


def expert_layer(m, moe, config, first_held=None, with_shared=True):
    """[n, d] -> [n, d]: the held experts' part of the layer, each
    expert on every token (a dense gather), and the shared expert."""
    first = (config["first_expert_held"] if first_held is None
             else first_held)
    weights, chosen = route(m, moe, config)
    w1, w3, w2 = moe["experts"]
    out = jnp.zeros_like(m)
    for e in range(w1.shape[0]):
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        out = out + w_e[:, None] * swiglu(m, (w1[e], w3[e], w2[e]))
    if with_shared and moe["shared"] is not None:
        out = out + swiglu(m, moe["shared"])
    return out, chosen


def _sequence(w, ids, config):
    """One sequence [L] -> (logits [L, V], chosen experts per layer)."""
    eps, d = config["rms_norm_eps"], config["hidden_size"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    h = w["embed"][ids]
    if config["mup_enabled"]:
        h = h * jnp.sqrt(float(d))
    routing = []
    for layer, kind in zip(w["layers"], config["layer_types"]):
        sliding = kind == "sliding_attention"
        a = rms_norm(h, layer["norms"]["input_norm"], eps)
        q = _mm(a, layer["wq"]).reshape(-1, heads, config["head_dim"])
        k = _mm(a, layer["wk"]).reshape(-1, kv_heads, config["head_dim"])
        v = _mm(a, layer["wv"]).reshape(-1, kv_heads, config["head_dim"])
        q = rms_norm(q, layer["q_norm"], eps)
        k = rms_norm(k, layer["k_norm"], eps)
        if sliding:
            q, k = rope(q, config["rope_theta"]), rope(k, config["rope_theta"])
        o = attention(q, k, v, config["sliding_window"] if sliding else None)
        o = o.reshape(o.shape[0], -1) * jax.nn.sigmoid(_mm(a, layer["wg"]))
        h = h + rms_norm(_mm(o, layer["wo"]),
                         layer["norms"]["post_attention_norm"], eps)
        m = rms_norm(h, layer["norms"]["pre_mlp_norm"], eps)
        if "moe" in layer:
            f, chosen = expert_layer(m, layer["moe"], config)
            routing.append(chosen)
        else:
            f = swiglu(m, layer["mlp"])
        h = h + rms_norm(f, layer["norms"]["post_mlp_norm"], eps)
    return _mm(rms_norm(h, w["final_norm"], eps), w["head"]), routing


def _ids(x):
    return jnp.asarray(x["input_ids"] if isinstance(x, dict) else x,
                       jnp.int32)


def forward(variables: dict, x, config: dict, with_routing: bool = False):
    """float32 logits [rows, L, V]; with ``with_routing`` also each
    expert layer's chosen experts [rows, L, k]."""
    with jax.default_matmul_precision("highest"):
        w = weights_from_program(variables)
        done = [_sequence(w, row, config) for row in _ids(x)]
    logits = jnp.stack([d[0] for d in done])
    if not with_routing:
        return logits
    return logits, [jnp.stack(layers) for layers in zip(*(d[1] for d in done))]


def loss(variables: dict, x, y, config: dict):
    """Mean over positions of the next-token cross-entropy."""
    logits = forward(variables, x, config)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(y, jnp.int32)[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def loss_and_grads(variables: dict, x, y, config: dict):
    """(loss, gradients in the tree of ``variables["params"]``)."""
    def of(params):
        return loss({**variables, "params": params}, x, y, config)

    return jax.value_and_grad(of)(variables["params"])
