"""Plain reference: the Moonlight-16B-A3B (``deepseek_v3``) decoder,
forward pass, next-token loss and gradients in float32 ``jax.numpy``
with ``default_matmul_precision("highest")``. No kernel, no sort, no
cache, nothing absorbed: keys and values are expanded from the latent
for every position, the rotary key is repeated to every head, attention
materialises its scores for a block of query rows at a time (so that
L = 8192 fits beside the program), and the expert layer loops over the
experts held, each on every token, weighted by what the router gave it.

Per layer (``benchmark/configs/moonlight-16b-a3b.json`` lists what no
key of the published config carries, under ``assumed``):

    a            = RMSNorm(h)
    q            = a Wq                        -> [L, H, nope + rot]
    c | k_r      = a Wkva                      -> [L, latent] | [L, rot]
    k_nope | v   = RMSNorm(c) Wkvb             -> [L, H, nope] | [L, H, v]
    q_rot, k_rot = RoPE(q[..., nope:]), RoPE(k_r)    k_rot: one head
    s_ij = (q_nope_i . k_nope_j + q_rot_i . k_rot_j) / sqrt(nope + rot),
           j <= i
    h = h + (softmax(s) v) Wo
    m = RMSNorm(h)
    dense layer:  h = h + (silu(m W1) * (m W3)) W2
    expert layer: r = sigmoid(m Wr); S = top_k(r + b);
                  w_e = r_e / (sum_S r + 1e-20) * routed_scaling_factor
                  h = h + SwiGLU_shared(m) + sum_{e in S, e held} w_e SwiGLU_e(m)
    logits = RMSNorm(h) W_head,  h0 = Embed[ids]

Departures from the published modelling code, each with no effect on
what is compared: RoPE pairs dimension i with i + rot/2 (rotate-half)
where the published code pairs 2i with 2i + 1 -- with weights from a
seed that is a fixed permutation of the rotary columns of Wq and Wkva,
on q and k alike, and leaves every score as it is; the two shared
experts are one SwiGLU of twice the width, which is what they compute;
the ``seq_aux`` balance loss is left out (no training code published).

It is given the same share as the program: the experts
``first_expert_held .. + n_routed_experts`` of
``n_routed_experts_routed_over`` and ``vocab_size`` rows of the
vocabulary. What absent experts would add is left out. Nothing is
shared with the program's model code; only :func:`weights_from_program`
knows the program's parameter names.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

QUERY_ROWS = 512        # attention scores exist for this many rows at once
_ROUND_OPERANDS_TO = None
# Ways to get the mathematics wrong, one at a time: the readings that
# show the cell's tolerance would catch each (``faulty``).
FAULTS = ("scale_by_nope_only", "no_rope", "no_latent_norm",
          "no_route_scale", "five_experts", "one_shared_expert")
_FAULT = None


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


@contextlib.contextmanager
def faulty(fault: str):
    """Inside, the reference makes one mistake of ``FAULTS``; what it
    then gives against itself must land over the cell's tolerance."""
    global _FAULT
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    before, _FAULT = _FAULT, fault
    try:
        yield
    finally:
        _FAULT = before


def _rounded(a):
    if _ROUND_OPERANDS_TO is None:
        return a
    return a.astype(_ROUND_OPERANDS_TO).astype(jnp.float32)


def _mm(a, b):
    return _rounded(a) @ _rounded(b)


def weights_from_program(variables: dict) -> dict:
    """The program's ``LatentDecoderModule`` tree -> neutral names.
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
            "input_norm": f32(lp["input_norm"]["scale"]),
            "pre_mlp_norm": f32(lp["pre_mlp_norm"]["scale"]),
            "wq": f32(a["q"]["kernel"]),
            "wkva": f32(a["kv_down"]["kernel"]),
            "latent_norm": f32(a["latent_norm"]["scale"]),
            "wkvb": f32(a["kv_up"]["kernel"]),
            "wo": f32(a["out"]["kernel"]),
        }
        if "moe" in lp:
            moe = lp["moe"]
            layer["moe"] = {
                "router": f32(moe["router"]["kernel"]),
                "bias": f32(state[name]["moe"]["bias"]),
                "experts": tuple(f32(moe[k]) for k in ("w1", "w3", "w2")),
                "shared": swiglu(moe["shared"]),
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
    if _FAULT == "no_rope":
        return x
    l, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(l, dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], -1)[:, None]       # [L, 1, D]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)


def attention(q, k, v, scale):
    """q, k [L, H, D], v [L, H, Dv] -> [L, H, Dv]; causal."""
    l = q.shape[0]
    out = []
    for start in range(0, l, QUERY_ROWS):
        rows = jnp.arange(start, min(start + QUERY_ROWS, l))[:, None]
        s = jnp.einsum("qhd,khd->hqk",
                       _rounded(q[start:start + QUERY_ROWS]), _rounded(k))
        s = jnp.where((jnp.arange(l)[None] <= rows)[None], s * scale,
                      -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd",
                              _rounded(jax.nn.softmax(s, -1)), _rounded(v)))
    return jnp.concatenate(out, 0)


def latent_attention(a, layer, config):
    """[L, d] -> [L, d]: the attention branch before the residual."""
    heads = config["num_attention_heads"]
    nope, rot = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    latent = config["kv_lora_rank"]
    q = _mm(a, layer["wq"]).reshape(-1, heads, nope + rot)
    down = _mm(a, layer["wkva"])
    c = down[:, :latent]
    if _FAULT != "no_latent_norm":
        c = rms_norm(c, layer["latent_norm"], config["rms_norm_eps"])
    kv = _mm(c, layer["wkvb"]).reshape(-1, heads, nope + config["v_head_dim"])
    k_rot = rope(down[:, None, latent:], config["rope_theta"])
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], config["rope_theta"])], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.repeat(k_rot, heads, axis=1)], -1)
    width = nope if _FAULT == "scale_by_nope_only" else nope + rot
    o = attention(q, k, kv[..., nope:], 1.0 / jnp.sqrt(float(width)))
    return _mm(o.reshape(o.shape[0], -1), layer["wo"])


def swiglu(m, weights):
    w1, w3, w2 = weights
    return _mm(jax.nn.silu(_mm(m, w1)) * _mm(m, w3), w2)


def route(m, moe, config):
    """Weights [n, k] and expert ids [n, k] over all routed experts."""
    r = jax.nn.sigmoid(m @ moe["router"])       # float32 at any setting
    top_k = config["num_experts_per_tok"] - (_FAULT == "five_experts")
    _, chosen = jax.lax.top_k(r + moe["bias"], top_k)
    picked = jnp.take_along_axis(r, chosen, -1)
    if config["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    if _FAULT != "no_route_scale":
        picked = picked * config["routed_scaling_factor"]
    return picked, chosen


def expert_layer(m, moe, config, first_held=None, with_shared=True):
    """[n, d] -> [n, d]: the held experts' part of the layer, each
    expert on every token (a dense gather), and the shared experts."""
    first = (config["first_expert_held"] if first_held is None
             else first_held)
    weights, chosen = route(m, moe, config)
    w1, w3, w2 = moe["experts"]
    out = jnp.zeros_like(m)
    for e in range(w1.shape[0]):
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        out = out + w_e[:, None] * swiglu(m, (w1[e], w3[e], w2[e]))
    if with_shared:
        s1, s3, s2 = moe["shared"]
        if _FAULT == "one_shared_expert":
            width = config["moe_intermediate_size"]
            s1, s3, s2 = s1[:, :width], s3[:, :width], s2[:width]
        out = out + swiglu(m, (s1, s3, s2))
    return out, chosen


def _sequence(w, ids, config):
    """One sequence [L] -> (logits [L, V], chosen experts per layer)."""
    eps = config["rms_norm_eps"]
    h = w["embed"][ids]
    routing = []
    for layer in w["layers"]:
        h = h + latent_attention(rms_norm(h, layer["input_norm"], eps),
                                 layer, config)
        m = rms_norm(h, layer["pre_mlp_norm"], eps)
        if "moe" in layer:
            f, chosen = expert_layer(m, layer["moe"], config)
            routing.append(chosen)
        else:
            f = swiglu(m, layer["mlp"])
        h = h + f
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
