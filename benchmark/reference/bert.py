"""Plain reference: BERT encoder + SQuAD span head, forward pass in
float32 ``jax.numpy`` with ``default_matmul_precision("highest")``.

Follows Devlin et al. 2018 / google-research/bert ``modeling.py`` in
eval mode: token + position embeddings (segment embeddings only when
segment ids are given), LayerNorm(eps 1e-12), post-LN blocks with
softmax(QK^T / sqrt(d)) V attention over all positions, erf GELU, and a
dense [H, 2] head whose two columns are the start and end logits.
Nothing is shared with the program's model code; only
:func:`weights_from_program` knows the program's parameter names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def weights_from_program(variables: dict) -> dict:
    """The program's ``BERTForSQuAD`` tree -> neutral names. The fused
    QKV kernel [H, 3, H] is split into its three matrices."""
    p = variables["params"]["squad"]
    b = p["bert"]

    def dense(d):
        return np.asarray(d["kernel"], np.float32), np.asarray(
            d["bias"], np.float32)

    def norm(d):
        return np.asarray(d["scale"], np.float32), np.asarray(
            d["bias"], np.float32)

    layers = []
    i = 0
    while f"encoder_{i}" in b:
        e = b[f"encoder_{i}"]
        qkv_w, qkv_b = dense(e["attention"]["qkv"])
        layers.append({
            "q": (qkv_w[:, 0], qkv_b[0]), "k": (qkv_w[:, 1], qkv_b[1]),
            "v": (qkv_w[:, 2], qkv_b[2]),
            "o": dense(e["attention"]["proj"]), "ln1": norm(e["ln_attn"]),
            "ffn_in": dense(e["ffn_in"]), "ffn_out": dense(e["ffn_out"]),
            "ln2": norm(e["ln_ffn"]),
        })
        i += 1
    return {"tok": np.asarray(b["token_embed"]["embedding"], np.float32),
            "pos": np.asarray(b["position_embed"], np.float32),
            "emb_ln": norm(b["embed_ln"]), "layers": layers,
            "head": dense(p["head"])}


def _layer_norm(x, scale_bias, eps):
    scale, bias = scale_bias
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _dense(x, wb):
    return x @ wb[0] + wb[1]


def encoder(weights: dict, ids, n_head: int, eps: float = 1e-12):
    seq = ids.shape[1]
    h = weights["tok"][ids] + weights["pos"][None, :seq]
    h = _layer_norm(h, weights["emb_ln"], eps)
    for lw in weights["layers"]:
        b, l, d = h.shape

        def heads(t):
            return t.reshape(b, l, n_head, d // n_head).transpose(0, 2, 1, 3)

        q, k, v = (heads(_dense(h, lw[n])) for n in "qkv")
        scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(d // n_head)
        ctx = jax.nn.softmax(scores, axis=-1) @ v
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, l, d)
        h = _layer_norm(h + _dense(ctx, lw["o"]), lw["ln1"], eps)
        ffn = _dense(jax.nn.gelu(_dense(h, lw["ffn_in"]), approximate=False),
                     lw["ffn_out"])
        h = _layer_norm(h + ffn, lw["ln2"], eps)
    return h


def forward(variables: dict, x: dict, config: dict):
    """(start_logits, end_logits), each [B, L] float32."""
    weights = weights_from_program(variables)
    ids = jnp.asarray(x["input_ids"], jnp.int32)

    @jax.jit
    def run(weights, ids):
        with jax.default_matmul_precision("highest"):
            h = encoder(weights, ids, int(config["num_attention_heads"]),
                        float(config["layer_norm_eps"]))
            logits = _dense(h, weights["head"])
        return logits[..., 0], logits[..., 1]

    return jax.device_get(run(weights, ids))
