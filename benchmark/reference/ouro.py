"""Plain reference: the looped decoder of Ouro (``model_type: ouro``;
"Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): every pass's logits, the exit distribution, the
stage I loss and its gradients in float32 ``jax.numpy`` with
``default_matmul_precision("highest")``. No kernel, no scan, no
rematerialisation: a Python loop over the passes and the layers, the
scores of a block of query rows held ([rows, L] at most) a block at a
time so that L = 8192 fits beside the program, and the loss's logits a
block of rows at a time.

The same ``n`` layers, with the same weights, run ``T =
total_ut_steps`` times (``benchmark/configs/ouro-2.6b.json`` lists what
no key of the published config carries, under ``assumed``):

    u = Embed[ids]
    for t = 1 .. T:
        for each layer:
            a = RMSNorm_1(u);  q, k, v = a Wq, a Wk, a Wv;  q, k <- RoPE
            u = u + RMSNorm_2( softmax_causal(q k^T / sqrt(128)) v  Wo )
            m = RMSNorm_3(u)
            u = u + RMSNorm_4( (silu(m W1) * (m W3)) W2 )
        h_t = RMSNorm_f(u);  u = h_t
        z_t = h_t W_head;  lambda_t = sigmoid(h_t . w_g + b_g)
    S_0 = 1;  p_t = lambda_t S_{t-1},  S_t = S_{t-1} - p_t;  p_T = S_{T-1}
    loss = mean over tokens of  sum_t p_t CE(z_t, y)  -  beta H(p)

Departures from the published modelling code (written from knowledge of
``modeling_ouro.py`` and the paper; there is no network here), each
listed in the configuration's ``assumed``: RoPE pairs dimension i with
i + 64 (rotate-half), a fixed permutation of Wq's and Wk's columns under
weights from a seed; the loss is the paper's stage I objective with a
uniform prior on the exit step (its KL term is the entropy up to a
constant) at ``beta``; attention runs across document boundaries.

Nothing is shared with the program's model code; only
:func:`weights_from_program` knows the program's parameter names.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

QUERY_ROWS = 512        # attention scores exist for this many rows at once
LOSS_ROWS = 1024        # the loss's logits exist for this many rows at once
_ROUND_OPERANDS_TO = None
# Ways to get the mathematics wrong, one at a time: the readings that
# show the cell's tolerance would catch each (``faulty``).
FAULTS = ("three_passes", "norm_after_loop", "no_closing_norm", "no_rope",
          "untied_pass")
_FAULT = None
UNTIED_BY = 0.05        # ``untied_pass``: the second pass's matrices x 1.05


@contextlib.contextmanager
def operands_rounded_to(dtype):
    """Inside, every matrix product's operands are rounded to ``dtype``
    first (the products themselves stay float32). For the reading that
    sets the cell's tolerance from below: this reference in the next
    precision under the configuration's (``float8_e4m3fn`` under
    bfloat16) must come out as NOT correct."""
    global _ROUND_OPERANDS_TO
    before, _ROUND_OPERANDS_TO = _ROUND_OPERANDS_TO, dtype
    try:
        yield
    finally:
        _ROUND_OPERANDS_TO = before


@contextlib.contextmanager
def faulty(fault: str):
    """Inside, the reference makes one mistake of ``FAULTS``."""
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
    """The program's ``LoopedDecoderModule`` tree -> neutral names.
    Traceable, so gradients come back in the program's own tree."""
    p = variables["params"]

    def f32(a):
        return jnp.asarray(a, jnp.float32)

    stack, layers = p["stack"], []
    while f"layer_{len(layers)}" in stack:
        lp = stack[f"layer_{len(layers)}"]
        layers.append({
            "norms": tuple(f32(lp[name]["scale"]) for name in (
                "input_norm", "post_attention_norm", "pre_mlp_norm",
                "post_mlp_norm")),
            "attention": tuple(f32(lp["attention"][k]["kernel"])
                               for k in ("q", "k", "v", "out")),
            "mlp": tuple(f32(lp["mlp"][k]["kernel"])
                         for k in ("w1", "w3", "w2")),
        })
    return {"embed": f32(p["embed"]["embedding"]), "layers": layers,
            "final_norm": f32(stack["final_norm"]["scale"]),
            "head": f32(p["head"]),
            "gate": (f32(p["exit_gate_kernel"]), f32(p["exit_gate_bias"]))}


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


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


def causal_attention(q, k, v):
    """q, k, v [L, H, D] -> [L, H, D], a block of query rows at a time."""
    l, scale = q.shape[0], q.shape[-1] ** -0.5
    out = []
    for start in range(0, l, QUERY_ROWS):
        stop = min(start + QUERY_ROWS, l)
        s = jnp.einsum("qhd,khd->hqk", _rounded(q[start:stop]),
                       _rounded(k[:stop])) * scale
        keep = jnp.arange(stop)[None] <= jnp.arange(start, stop)[:, None]
        s = jnp.where(keep[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd",
                              _rounded(jax.nn.softmax(s, -1)),
                              _rounded(v[:stop])))
    return jnp.concatenate(out, 0)


def layer_forward(u, layer, config):
    """[L, d] -> [L, d]: one sandwich-norm block."""
    eps = config["rms_norm_eps"]
    heads, d_head = config["num_attention_heads"], config["head_dim"]
    n1, n2, n3, n4 = layer["norms"]
    wq, wk, wv, wo = layer["attention"]
    w1, w3, w2 = layer["mlp"]
    a = rms_norm(u, n1, eps)
    q, k, v = (_mm(a, w).reshape(-1, heads, d_head) for w in (wq, wk, wv))
    o = causal_attention(rope(q, config["rope_theta"]),
                         rope(k, config["rope_theta"]), v)
    o = _mm(o.reshape(o.shape[0], -1), wo)
    u = u + (o if _FAULT == "no_closing_norm" else rms_norm(o, n2, eps))
    m = rms_norm(u, n3, eps)
    f = _mm(jax.nn.silu(_mm(m, w1)) * _mm(m, w3), w2)
    return u + rms_norm(f, n4, eps)


def _passes(w, config) -> list:
    """The layers of each pass: the same list ``T`` times, unless the
    weights carry ``passes`` (a list of ``T`` lists: untied copies)."""
    steps = config["total_ut_steps"] - (_FAULT == "three_passes")
    passes = w.get("passes") or [w["layers"]] * steps
    if _FAULT == "untied_pass":
        scaled = [{**layer, "attention": tuple(
            a * (1 + UNTIED_BY) for a in layer["attention"]), "mlp": tuple(
            a * (1 + UNTIED_BY) for a in layer["mlp"])}
            for layer in passes[1]]
        passes = [passes[0], scaled] + list(passes[2:])
    return passes[:steps]


def states(w, ids, config) -> list:
    """One sequence [L] -> every pass's ``h_t`` [L, d]."""
    eps = config["rms_norm_eps"]
    u, out = w["embed"][ids], []
    for layers in _passes(w, config):
        for layer in layers:
            u = layer_forward(u, layer, config)
        if _FAULT == "norm_after_loop":
            # the norm once, after the loop: the raw stream loops on
            out.append(rms_norm(u, w["final_norm"], eps))
        else:
            u = rms_norm(u, w["final_norm"], eps)
            out.append(u)
    return out


def exit_log_distribution(hs, gate) -> list:
    """Every pass's log exit probability a token, [L] each, from
    ``log sigmoid`` of the gates and of their negatives: a gate that
    has run to one end leaves ``log p`` finite where ``p`` itself is 0
    (and ``p log p`` then 0 with a gradient of 0, not NaN)."""
    w_g, b_g = gate
    log_survive, log_p = jnp.zeros(hs[0].shape[0]), []
    for h in hs[:-1]:
        g = _mm(h, w_g[:, None])[:, 0] + b_g[0]
        log_p.append(jax.nn.log_sigmoid(g) + log_survive)
        log_survive = log_survive + jax.nn.log_sigmoid(-g)
    return log_p + [log_survive]


def exit_distribution(hs, gate) -> list:
    """Every pass's exit probability a token, [L] each."""
    return [jnp.exp(log_p) for log_p in exit_log_distribution(hs, gate)]


def _ids(x):
    return jnp.asarray(x["input_ids"] if isinstance(x, dict) else x,
                       jnp.int32)


def forward(variables: dict, x, config: dict):
    """float32 ``z_T`` [rows, L, V]: the last pass's logits, what
    ``model.predict`` returns."""
    with jax.default_matmul_precision("highest"):
        w = weights_from_program(variables)
        return jnp.stack([_mm(states(w, row, config)[-1], w["head"])
                          for row in _ids(x)])


def forward_all(variables: dict, x, config: dict):
    """(every pass's logits [rows, T, L, V], the exit distribution
    [rows, T, L]): small sizes only."""
    with jax.default_matmul_precision("highest"):
        w = weights_from_program(variables)
        z, p = [], []
        for row in _ids(x):
            hs = states(w, row, config)
            z.append(jnp.stack([_mm(h, w["head"]) for h in hs]))
            p.append(jnp.stack(exit_distribution(hs, w["gate"])))
        return jnp.stack(z), jnp.stack(p)


def _cross_entropy(h, head, y):
    """[L]: the cross-entropy of ``y`` under ``h @ head``, the logits a
    block of rows at a time."""
    out = []
    for start in range(0, h.shape[0], LOSS_ROWS):
        z = _mm(h[start:start + LOSS_ROWS], head)
        picked = jnp.take_along_axis(
            z, y[start:start + LOSS_ROWS, None], -1)[:, 0]
        out.append(jax.nn.logsumexp(z, -1) - picked)
    return jnp.concatenate(out)


def weights_loss(w: dict, x, y, config: dict, beta: float):
    """The loss of neutral weights ``w`` (which may carry untied
    ``passes``)."""
    with jax.default_matmul_precision("highest"):
        y = jnp.asarray(y, jnp.int32)
        total = 0.0
        for row, targets in zip(_ids(x), y):
            hs = states(w, row, config)
            for h, log_p in zip(hs, exit_log_distribution(hs, w["gate"])):
                total = total + jnp.sum(jnp.exp(log_p) * (
                    _cross_entropy(h, w["head"], targets) + beta * log_p))
        return total / y.size


def loss(variables: dict, x, y, config: dict, beta=None):
    """``y`` [rows, L] is each position's next token; ``beta`` is the
    configuration's where none is given."""
    beta = config["exit_entropy_beta"] if beta is None else beta
    return weights_loss(weights_from_program(variables), x, y, config, beta)


def loss_and_grads(variables: dict, x, y, config: dict, beta=None):
    """(loss, gradients in the tree of ``variables["params"]``)."""
    def of(params):
        return loss({**variables, "params": params}, x, y, config, beta)

    return jax.value_and_grad(of)(variables["params"])
