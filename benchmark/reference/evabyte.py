"""Plain reference: the EvaByte decoder (``model_type: evabyte``,
``attention_class: eva``), forward pass, multi-byte loss and gradients
in float32 ``jax.numpy`` with ``default_matmul_precision("highest")``.
No kernel, no rematerialisation: the joint scores of a block of query
rows over its window's token keys and over the summaries of the earlier
windows are held ([rows, L] and [rows, L / 16] at most), a block at a
time so that L = 8192 fits beside the program.

Per layer, per head, ``s = head_dim ** -0.5``
(``benchmark/configs/evabyte-6.5b.json`` lists what no key of the
published config carries, under ``assumed``):

    x  = RMSNorm1p(h)                       y * (1 + w)
    q, k, v = x Wq, x Wk, x Wv;  q, k <- RoPE(theta) on all dims
    chunk m = positions 16 m .. 16 m + 15;  window w(i) = i // 2048
    pi_j  = softmax over chunk m's positions of  s * (k_j . phi)
    k~_m  = sum_j pi_j k_j + mu             v~_m = sum_j pi_j v_j
    S_i = { j : 2048 w(i) <= j <= i }       R_i = { m : m < 128 w(i) }
    o_i = softmax over S_i and R_i together of s * q_i . (k_j | k~_m),
          applied to (v_j | v~_m)
    h = h + o Wo;   h = h + (silu(x' W1) * (x' W3)) W2,  x' = RMSNorm1p(h)
    logits[t, n] = RMSNorm1p(h_L)[t] W_head[:, n]     n = 0 .. 7
    loss = mean over n and the t with t + 1 + n <= L of the
           cross-entropy of byte t + 1 + n under logits[t, n]

Departures from the published modelling code (written from knowledge of
``eva.py`` / ``eva_prep_kv_kernel.py`` / ``eva_agg_kernel.py``; there is
no network here), each listed in the configuration's ``assumed``: norm
statistics are float32 although ``fp32_ln`` is false (the safer side);
the loss weighs the eight heads alike; RoPE pairs dimension i with
i + 64 (rotate-half), a fixed permutation of Wq's and Wk's columns
under weights from a seed.

Nothing is shared with the program's model code; only
:func:`weights_from_program` knows the program's parameter names.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

QUERY_ROWS = 512        # attention scores exist for this many rows at once
_ROUND_OPERANDS_TO = None
# Ways to get the mathematics wrong, one at a time: the readings that
# show the cell's tolerance would catch each (``faulty``). The last is a
# fault of the loss, which only the loss and its gradients show.
FAULTS = ("no_mu", "mean_pooling", "own_window_summaries", "sliding_window",
          "no_rope", "no_unit_offset", "labels_shifted")
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
    """The program's ``ByteDecoderModule`` tree -> neutral names.
    Traceable, so gradients come back in the program's own tree."""
    p = variables["params"]

    def f32(a):
        return jnp.asarray(a, jnp.float32)

    layers = []
    while f"layer_{len(layers)}" in p:
        lp = p[f"layer_{len(layers)}"]
        a = lp["attention"]
        layers.append({
            "input_norm": f32(lp["input_norm"]["scale"]),
            "pre_mlp_norm": f32(lp["pre_mlp_norm"]["scale"]),
            "wq": f32(a["q"]["kernel"]), "wk": f32(a["k"]["kernel"]),
            "wv": f32(a["v"]["kernel"]), "wo": f32(a["out"]["kernel"]),
            "phi": f32(a["adaptive_phi"]), "mu": f32(a["adaptive_mu_k"]),
            "mlp": tuple(f32(lp["mlp"][k]["kernel"])
                         for k in ("w1", "w3", "w2")),
        })
    return {"embed": f32(p["embed"]["embedding"]), "layers": layers,
            "final_norm": f32(p["final_norm"]["scale"]),
            "head": f32(p["head"])}


def rms_norm_1p(x, w, eps):
    """``norm_add_unit_offset``: the parameter is the scale less 1."""
    scale = w if _FAULT == "no_unit_offset" else 1.0 + w
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


def chunk_summaries(k, v, phi, mu, chunk, scale):
    """k, v [L, H, D] -> k~, v~ [L / chunk, H, D]."""
    l, h, d = k.shape
    kc = k.reshape(l // chunk, chunk, h, d)
    vc = v.reshape(l // chunk, chunk, h, d)
    if _FAULT == "mean_pooling":
        pi = jnp.full(kc.shape[:3], 1.0 / chunk)
    else:
        pi = jax.nn.softmax(scale * jnp.einsum("mchd,hd->mch", kc, phi), 1)
    k_sum = jnp.einsum("mch,mchd->mhd", pi, kc)
    if _FAULT != "no_mu":
        k_sum = k_sum + mu
    return k_sum, jnp.einsum("mch,mchd->mhd", pi, vc)


def eva_attention(q, k, v, k_sum, v_sum, window, chunk, scale):
    """q, k, v [L, H, D], k_sum, v_sum [L / chunk, H, D] -> [L, H, D]:
    the joint softmax, a block of rows of one window at a time."""
    l = q.shape[0]
    per = window // chunk
    rows_at_once = min(QUERY_ROWS, window)
    out = []
    for start in range(0, l, rows_at_once):
        stop = min(start + rows_at_once, l)
        w = start // window
        rows = jnp.arange(start, stop)[:, None]
        first = w * window
        if _FAULT == "sliding_window":
            first = max(0, start - window + 1)
        cols = jnp.arange(first, stop)[None]
        keep = cols <= rows
        if _FAULT == "sliding_window":
            keep &= rows - cols < window
        n_sum = (w + (_FAULT == "own_window_summaries")) * per
        keep = jnp.concatenate(
            [jnp.ones((stop - start, n_sum), bool), keep], axis=1)
        keys = jnp.concatenate([k_sum[:n_sum], k[first:stop]], 0)
        values = jnp.concatenate([v_sum[:n_sum], v[first:stop]], 0)
        s = jnp.einsum("qhd,khd->hqk", _rounded(q[start:stop]),
                       _rounded(keys)) * scale
        s = jnp.where(keep[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd",
                              _rounded(jax.nn.softmax(s, -1)),
                              _rounded(values)))
    return jnp.concatenate(out, 0)


def attention_branch(x, layer, config):
    """[L, d] -> [L, d]: the attention branch before the residual."""
    heads = config["num_attention_heads"]
    d_head = config["hidden_size"] // heads
    scale = d_head ** -0.5
    q, k, v = (_mm(x, layer[name]).reshape(-1, heads, d_head)
               for name in ("wq", "wk", "wv"))
    q, k = rope(q, config["rope_theta"]), rope(k, config["rope_theta"])
    k_sum, v_sum = chunk_summaries(k, v, layer["phi"], layer["mu"],
                                   config["chunk_size"], scale)
    o = eva_attention(q, k, v, k_sum, v_sum, config["window_size"],
                      config["chunk_size"], scale)
    return _mm(o.reshape(o.shape[0], -1), layer["wo"])


def swiglu(m, weights):
    w1, w3, w2 = weights
    return _mm(jax.nn.silu(_mm(m, w1)) * _mm(m, w3), w2)


def _sequence(w, ids, config):
    """One sequence [L] -> logits [L, heads, V]."""
    eps = config["rms_norm_eps"]
    h = w["embed"][ids]
    for layer in w["layers"]:
        h = h + attention_branch(
            rms_norm_1p(h, layer["input_norm"], eps), layer, config)
        h = h + swiglu(rms_norm_1p(h, layer["pre_mlp_norm"], eps),
                       layer["mlp"])
    logits = _mm(rms_norm_1p(h, w["final_norm"], eps), w["head"])
    return logits.reshape(logits.shape[0], config["num_pred_heads"],
                          config["vocab_size"])


def _ids(x):
    return jnp.asarray(x["input_ids"] if isinstance(x, dict) else x,
                       jnp.int32)


def forward(variables: dict, x, config: dict):
    """float32 logits [rows, L, num_pred_heads, V]."""
    with jax.default_matmul_precision("highest"):
        w = weights_from_program(variables)
        return jnp.stack([_sequence(w, row, config) for row in _ids(x)])


def loss(variables: dict, x, y, config: dict):
    """``y`` [rows, L] is each position's next byte. Head n at t is
    scored on byte t + 1 + n = y[t + n], for the t that have one; the
    mean over all such (t, n), a loop over the heads."""
    logits = forward(variables, x, config)
    y = jnp.asarray(y, jnp.int32)
    rows, l = y.shape
    total, count = 0.0, 0
    for n in range(logits.shape[2]):
        ahead = n + (_FAULT == "labels_shifted" and n == 1)
        scored = logits[:, :l - ahead, n]
        picked = jnp.take_along_axis(scored, y[:, ahead:, None], -1)[..., 0]
        total = total + jnp.sum(jax.nn.logsumexp(scored, -1) - picked)
        count += rows * (l - ahead)
    return total / count


def loss_and_grads(variables: dict, x, y, config: dict):
    """(loss, gradients in the tree of ``variables["params"]``)."""
    def of(params):
        return loss({**variables, "params": params}, x, y, config)

    return jax.value_and_grad(of)(variables["params"])
