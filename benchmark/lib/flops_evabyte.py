"""Operations and bytes of a training step of the EvaByte layers in
``benchmark/configs/evabyte-6.5b.json``, from the file's widths only --
never from what the program executes, so rematerialised forward passes,
block padding and the form of the kernels do not count. Same
conventions as ``flops.py``: a multiply-add is 2 operations, backward
costs twice the forward, lookups count 0.

The model is dense: every parameter outside the embedding table
multiplies every byte. EVA's attention reads, for a query in window
``w``, the token keys of its own window up to itself and one summary a
chunk of every earlier window: ``attention_pairs`` counts the two kinds
apart. The pooling that makes the summaries is bandwidth:
``chunk_summaries_min_bytes`` is the least it can move.
"""

from benchmark.lib.flops import _optimizer_bytes


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def layer_params(config: dict) -> int:
    """q, k, v, o; SwiGLU's three; two norms; EVA's ``adaptive_phi`` and
    ``adaptive_mu_k`` a head: 202,391,552 at the published widths."""
    d = config["hidden_size"]
    return (4 * d * d + 3 * d * config["intermediate_size"] + 2 * d
            + 2 * config["num_attention_heads"] * head_dim(config))


def head_params(config: dict) -> int:
    """``num_pred_heads`` heads over the vocabulary: 10,485,760."""
    return (config["hidden_size"] * config["num_pred_heads"]
            * config["vocab_size"])


def params(config: dict) -> int:
    """Every parameter the chip holds (821,366,784 for the file)."""
    d = config["hidden_size"]
    return (config["num_hidden_layers"] * layer_params(config)
            + config["vocab_size"] * d + head_params(config) + d)


def matmul_params_per_token(config: dict) -> int:
    """Parameters one byte's forward pass multiplies by: the layers'
    matrices and the heads (norms, ``phi`` and ``mu`` are no matmul)."""
    d = config["hidden_size"]
    return (config["num_hidden_layers"]
            * (4 * d * d + 3 * d * config["intermediate_size"])
            + head_params(config))


def attention_pairs(config: dict, seq: int) -> dict:
    """(query, key) pairs EVA's mask allows in one sequence and head:
    ``in_window`` (token keys) and ``summaries`` (query x chunk
    summaries of earlier windows). 8,392,704 + 1,572,864 at 8,192."""
    window, chunk = config["window_size"], config["chunk_size"]
    whole, rest = divmod(seq, window)
    in_window = whole * window * (window + 1) // 2 + rest * (rest + 1) // 2
    summaries = sum(min(window, seq - w * window) * w * (window // chunk)
                    for w in range(whole + (rest > 0)))
    return {"in_window": in_window, "summaries": summaries}


def attention_forward_flops(config: dict, seq: int) -> int:
    """One layer's QK^T and PV over the allowed pairs of both kinds,
    every head: the published work, whichever form of the kernel runs
    and whatever it pads (19.93 MFLOP a byte at 8,192)."""
    pairs = attention_pairs(config, seq)
    return ((pairs["in_window"] + pairs["summaries"]) * 4
            * head_dim(config) * config["num_attention_heads"])


def forward_flops_per_token(config: dict, seq: int) -> dict:
    """Forward FLOPs a byte, by part."""
    d = config["hidden_size"]
    layers = config["num_hidden_layers"]
    return {
        "projections": 2 * layers * 4 * d * d,
        "swiglu": 2 * layers * 3 * d * config["intermediate_size"],
        "heads": 2 * head_params(config),
        "attention": layers * attention_forward_flops(config, seq) / seq,
    }


def chunk_summaries_min_bytes(config: dict, seq: int, itemsize: int = 2) -> dict:
    """Least HBM bytes of one layer's pooling on one sequence: forward,
    k and v read and the summaries written; backward, the summaries'
    cotangents read, k and v read again and their cotangents written."""
    width = config["hidden_size"]            # heads x head_dim
    tokens = 2 * seq * width * itemsize
    summaries = 2 * (seq // config["chunk_size"]) * width * itemsize
    return {"forward": tokens + summaries,
            "backward": summaries + 2 * tokens}


def train(config: dict, data: dict) -> dict:
    seq = data["seq_len"]
    forward = (2 * matmul_params_per_token(config) * seq
               + config["num_hidden_layers"]
               * attention_forward_flops(config, seq))
    return {
        "flops_per_sample": 3 * forward,
        "min_bytes_per_step": (_optimizer_bytes(params(config), moments=2)
                               + data["batch"] * seq * 2 * 4),
    }
