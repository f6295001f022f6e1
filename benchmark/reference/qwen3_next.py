"""Plain reference: the Qwen3-Next-80B-A3B (``qwen3_next``) decoder,
forward pass, next-token loss with the routers' balance loss, and
gradients in float32 ``jax.numpy`` with
``default_matmul_precision("highest")``. No kernel, no sort, no chunk:
the gated delta rule is the token-by-token recurrence (a ``lax.scan``
over positions, the definition), attention materialises its scores for
a block of query rows at a time (so that L = 8192 fits beside the
program), and the expert layer loops over the experts held, each on
every token, weighted by what the router gave it.

Per layer (``benchmark/configs/qwen3-next-80b-a3b.json`` lists what no
key of the published config carries, under ``assumed``); every norm
but the gated one is zero-centred, ``x_hat (1 + w)``:

    a = RMSNorm(h)
    Gated DeltaNet layers (i + 1 not a multiple of full_attention_interval):
        q | k | v | z = a W_qkvz,  b | a' = a W_ba
        q | k | v = SiLU(causal depthwise conv, 4 taps)
        q = L2norm(q) / sqrt(d_k), k = L2norm(k), each repeated to the
            value heads (head j reads key head j // (n_v / n_k))
        beta = sigmoid(b), g = -exp(A_log) softplus(a' + dt_bias)
        S_t = e^{g_t} S_{t-1};  S_t += k_t (beta_t (v_t - S_t^T k_t))^T
        o_t = S_t^T q_t
        mix = (RMSNorm_dv(o) w * SiLU(z)) W_out
    gated attention layers:
        q, k, v, gate = a Wq, a Wk, a Wv, a Wg;  q, k = RMSNorm(q), RMSNorm(k)
        RoPE (rotate-half) on the leading partial_rotary_factor of a head
        mix = (softmax_{j <= i}(q k^T / sqrt(d)) v * sigmoid(gate)) Wo
    h = h + mix
    m = RMSNorm(h);  p = softmax(m Wr);  S = top_k(p);  w = p_S / sum p_S
    h = h + sigmoid(m w_sg) SwiGLU_shared(m)
          + sum_{e in S, e held} w_e SwiGLU_e(m)
    logits = RMSNorm(h) W_head,  h0 = Embed[ids]
    loss = CE + router_aux_loss_coef x mean over layers of
           E sum_e (count_e / tokens) mean_t p_te

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
# Ways to get the mathematics wrong, one at a time: the readings that
# show the cell's tolerance would catch each (``faulty``).
FAULTS = ("no_qk_l2norm", "no_decay", "beta_one", "no_short_conv",
          "no_output_gate", "rope_all_dims", "no_attention_gate",
          "no_shared_gate", "nine_experts", "no_renormalise")
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
    """The program's ``HybridDecoderModule`` tree -> neutral names.
    Traceable, so gradients come back in the program's own tree."""
    p = variables["params"]

    def f32(a):
        return jnp.asarray(a, jnp.float32)

    def kernel(d, name):
        return f32(d[name]["kernel"])

    layers = []
    while f"layer_{len(layers)}" in p:
        lp = p[f"layer_{len(layers)}"]
        moe = lp["moe"]
        layer = {
            "input_norm": f32(lp["input_norm"]["scale"]),
            "pre_mlp_norm": f32(lp["pre_mlp_norm"]["scale"]),
            "moe": {
                "router": kernel(moe, "router"),
                "experts": tuple(f32(moe[k]) for k in ("w1", "w3", "w2")),
                "shared": tuple(kernel(moe["shared"], k)
                                for k in ("w1", "w3", "w2")),
                "shared_gate": kernel(moe, "shared_gate"),
            },
        }
        if "linear_mixer" in lp:
            m = lp["linear_mixer"]
            layer["linear"] = {
                "qkvz": kernel(m, "in_proj_qkvz"),
                "ba": kernel(m, "in_proj_ba"),
                "conv": f32(m["conv"]), "A_log": f32(m["A_log"]),
                "dt_bias": f32(m["dt_bias"]), "norm": f32(m["norm"]),
                "out": kernel(m, "out_proj"),
            }
        else:
            a = lp["attention"]
            layer["attention"] = {
                **{k: kernel(a, k) for k in ("q", "k", "v", "gate", "out")},
                "q_norm": f32(a["q_norm"]["scale"]),
                "k_norm": f32(a["k_norm"]["scale"]),
            }
        layers.append(layer)
    return {"embed": f32(p["embed"]["embedding"]), "layers": layers,
            "final_norm": f32(p["final_norm"]["scale"]),
            "head": f32(p["head"])}


def rms_norm(x, offset_scale, eps):
    """Zero-centred: ``x_hat (1 + w)``."""
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + offset_scale)


def short_conv(x, w):
    """[R, L, C] causal depthwise convolution, w [K, C], w[K - 1] on
    the position itself."""
    if _FAULT == "no_short_conv":
        return x
    k, length = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, i:i + length] * w[i] for i in range(k))


def delta_rule(q, k, v, g, beta, state=None):
    """The recurrence, one position at a time: q, k [R, L, H, d_k],
    v [R, L, H, d_v], g, beta [R, L, H] -> (o [R, L, H, d_v], the last
    state [R, H, d_k, d_v]); ``state`` is the one before position 0
    (zeros where None)."""
    r, _, h, d_k = q.shape
    if state is None:
        state = jnp.zeros((r, h, d_k, v.shape[-1]), jnp.float32)

    def step(s, at):
        q_t, k_t, v_t, g_t, beta_t = at
        s = jnp.exp(g_t)[..., None, None] * s
        written = beta_t[..., None] * (
            v_t - jnp.einsum("rhkv,rhk->rhv", s, k_t))
        s = s + jnp.einsum("rhk,rhv->rhkv", k_t, written)
        return s, jnp.einsum("rhkv,rhk->rhv", s, q_t)

    state, out = jax.lax.scan(
        step, state, [jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)])
    return jnp.moveaxis(out, 0, 1), state


def _l2(x):
    if _FAULT == "no_qk_l2norm":
        return x
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def gated_delta_net(a, w, config):
    """[R, L, d] -> [R, L, d]: the linear mixer before the residual."""
    r, length, _ = a.shape
    h_k, h_v = config["linear_num_key_heads"], config["linear_num_value_heads"]
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    key_dim, value_dim = h_k * d_k, h_v * d_v
    qkvz = _mm(a, w["qkvz"])
    ba = _mm(a, w["ba"])
    qkv = jax.nn.silu(short_conv(qkvz[..., :2 * key_dim + value_dim],
                                 w["conv"]))
    z = qkvz[..., 2 * key_dim + value_dim:].reshape(r, length, h_v, d_v)

    def heads(x):
        return jnp.repeat(x.reshape(r, length, h_k, d_k), h_v // h_k, axis=2)

    q = _l2(heads(qkv[..., :key_dim])) / jnp.sqrt(float(d_k))
    k = _l2(heads(qkv[..., key_dim:2 * key_dim]))
    v = qkv[..., 2 * key_dim:].reshape(r, length, h_v, d_v)
    beta = jax.nn.sigmoid(ba[..., :h_v])
    if _FAULT == "beta_one":
        beta = jnp.ones_like(beta)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[..., h_v:] + w["dt_bias"])
    if _FAULT == "no_decay":
        g = jnp.zeros_like(g)
    o, _ = delta_rule(_rounded(q), _rounded(k), _rounded(v), g, beta)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                     + config["rms_norm_eps"]) * w["norm"]
    if _FAULT != "no_output_gate":
        o = o * jax.nn.silu(z)
    return _mm(o.reshape(r, length, value_dim), w["out"])


def rope(x, theta, dims):
    """[R, L, heads, D]: rotate-half on the leading ``dims`` of D,
    positions 0..L-1."""
    length = x.shape[1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dims, 2, dtype=jnp.float32)
                               / dims)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], -1)[:, None]   # [L, 1, dims]
    x_rot, x_pass = x[..., :dims], x[..., dims:]
    x1, x2 = jnp.split(x_rot, 2, axis=-1)
    x_rot = (x_rot * jnp.cos(angle)
             + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle))
    return jnp.concatenate([x_rot, x_pass], -1)


def attention(q, k, v, scale):
    """q [R, L, H, D], k, v [R, L, H_kv, D] -> [R, L, H, D]; causal,
    query head n reads KV head n // (H / H_kv)."""
    length, heads = q.shape[1], q.shape[2]
    k = jnp.repeat(k, heads // k.shape[2], axis=2)
    v = jnp.repeat(v, heads // v.shape[2], axis=2)
    out = []
    for start in range(0, length, QUERY_ROWS):
        rows = jnp.arange(start, min(start + QUERY_ROWS, length))[:, None]
        s = jnp.einsum("rqhd,rkhd->rhqk",
                       _rounded(q[:, start:start + QUERY_ROWS]), _rounded(k))
        s = jnp.where(jnp.arange(length)[None] <= rows, s * scale, -jnp.inf)
        out.append(jnp.einsum("rhqk,rkhd->rqhd",
                              _rounded(jax.nn.softmax(s, -1)), _rounded(v)))
    return jnp.concatenate(out, 1)


def gated_attention(a, w, config):
    """[R, L, d] -> [R, L, d]: the attention mixer before the residual."""
    r, length, _ = a.shape
    heads, kv_heads = config["num_attention_heads"], config[
        "num_key_value_heads"]
    dim, eps = config["head_dim"], config["rms_norm_eps"]
    q = rms_norm(_mm(a, w["q"]).reshape(r, length, heads, dim), w["q_norm"],
                 eps)
    k = rms_norm(_mm(a, w["k"]).reshape(r, length, kv_heads, dim),
                 w["k_norm"], eps)
    v = _mm(a, w["v"]).reshape(r, length, kv_heads, dim)
    dims = (dim if _FAULT == "rope_all_dims"
            else int(dim * config["partial_rotary_factor"]))
    q, k = rope(q, config["rope_theta"], dims), rope(k, config["rope_theta"],
                                                     dims)
    o = attention(q, k, v, 1.0 / jnp.sqrt(float(dim))).reshape(r, length, -1)
    if _FAULT != "no_attention_gate":
        o = o * jax.nn.sigmoid(_mm(a, w["gate"]))
    return _mm(o, w["out"])


def swiglu(m, weights):
    w1, w3, w2 = weights
    return _mm(jax.nn.silu(_mm(m, w1)) * _mm(m, w3), w2)


def route(m, moe, config):
    """(weights [n, k], expert ids [n, k], probabilities [n, E]) over
    all routed experts."""
    p = jax.nn.softmax(m @ moe["router"], -1)      # float32 at any setting
    top_k = config["num_experts_per_tok"] - (_FAULT == "nine_experts")
    picked, chosen = jax.lax.top_k(p, top_k)
    if config["norm_topk_prob"] and _FAULT != "no_renormalise":
        picked = picked / picked.sum(-1, keepdims=True)
    return picked, chosen, p


def balance_loss(chosen, p, n_experts: int):
    """Switch Transformer's ``E sum_e f_e P_e``: ``f_e`` the tokens that
    chose ``e`` over all tokens, ``P_e`` its mean probability; the
    counts carry no gradient."""
    counts = jnp.sum(jax.nn.one_hot(chosen, n_experts), axis=(0, 1))
    return n_experts * jnp.sum(jax.lax.stop_gradient(counts / p.shape[0])
                               * jnp.mean(p, 0))


def expert_layer(m, moe, config, first_held=None, with_shared=True):
    """[n, d] -> ([n, d], chosen [n, k], balance loss): the held experts'
    part of the layer, each expert on every token (a dense gather), and
    the gated shared expert."""
    first = (config["first_expert_held"] if first_held is None
             else first_held)
    weights, chosen, p = route(m, moe, config)
    w1, w3, w2 = moe["experts"]
    out = jnp.zeros_like(m)
    for e in range(w1.shape[0]):
        w_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        out = out + w_e[:, None] * swiglu(m, (w1[e], w3[e], w2[e]))
    if with_shared:
        shared = swiglu(m, moe["shared"])
        if _FAULT != "no_shared_gate":
            shared = shared * jax.nn.sigmoid(_mm(m, moe["shared_gate"]))
        out = out + shared
    return out, chosen, balance_loss(chosen, p, moe["router"].shape[1])


def _decoder(w, ids, config):
    """ids [R, L] -> (logits [R, L, V], chosen experts per layer
    [R, L, k], the mean of the layers' balance losses)."""
    eps = config["rms_norm_eps"]
    h = w["embed"][ids]
    r, length, d = h.shape
    routing, balance = [], []
    for layer in w["layers"]:
        a = rms_norm(h, layer["input_norm"], eps)
        if "linear" in layer:
            h = h + gated_delta_net(a, layer["linear"], config)
        else:
            h = h + gated_attention(a, layer["attention"], config)
        m = rms_norm(h, layer["pre_mlp_norm"], eps).reshape(-1, d)
        f, chosen, aux = expert_layer(m, layer["moe"], config)
        h = h + f.reshape(r, length, d)
        routing.append(chosen.reshape(r, length, -1))
        balance.append(aux)
    logits = _mm(rms_norm(h, w["final_norm"], eps), w["head"])
    return logits, routing, sum(balance) / len(balance)


def _ids(x):
    return jnp.asarray(x["input_ids"] if isinstance(x, dict) else x,
                       jnp.int32)


def forward(variables: dict, x, config: dict, with_routing: bool = False):
    """float32 logits [rows, L, V]; with ``with_routing`` also each
    expert layer's chosen experts [rows, L, k]."""
    with jax.default_matmul_precision("highest"):
        logits, routing, _ = _decoder(weights_from_program(variables),
                                      _ids(x), config)
    return (logits, routing) if with_routing else logits


def loss(variables: dict, x, y, config: dict):
    """Mean over positions of the next-token cross-entropy, plus
    ``router_aux_loss_coef`` times the layers' mean balance loss."""
    with jax.default_matmul_precision("highest"):
        logits, _, balance = _decoder(weights_from_program(variables),
                                      _ids(x), config)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(y, jnp.int32)[..., None], -1)[..., 0]
    return (jnp.mean(jax.nn.logsumexp(logits, -1) - picked)
            + config["router_aux_loss_coef"] * balance)


def loss_and_grads(variables: dict, x, y, config: dict):
    """(loss, gradients in the tree of ``variables["params"]``)."""
    def of(params):
        return loss({**variables, "params": params}, x, y, config)

    return jax.value_and_grad(of)(variables["params"])
