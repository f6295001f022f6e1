"""Operations and bytes of a training step of the looped decoder in
``benchmark/configs/ouro-2.6b.json``, from the file's widths only --
never from what the program executes, so rematerialised forward passes,
block padding and the heads' blocks computed again do not count. Same
conventions as ``flops.py``: a multiply-add is 2 operations, backward
costs twice the forward, lookups count 0.

The model is dense and LOOPED: the ``num_hidden_layers`` layers run
``total_ut_steps`` times with the same weights, and the head runs after
every pass. So a layer's matrices multiply every token
``total_ut_steps`` times while they are held, and updated, once:
operations scale with passes x layers, parameters and the optimizer's
bytes with layers.
"""

from benchmark.lib.flops import _optimizer_bytes


def passes(config: dict) -> int:
    return config["total_ut_steps"]


def layer_matmul_params(config: dict) -> int:
    """q, k, v, o and SwiGLU's three: 51,380,224 at the published widths."""
    d = config["hidden_size"]
    return (4 * d * config["num_attention_heads"] * config["head_dim"]
            + 3 * d * config["intermediate_size"])


def layer_params(config: dict) -> int:
    """The matrices and four norms: 51,388,416."""
    return layer_matmul_params(config) + 4 * config["hidden_size"]


def head_params(config: dict) -> int:
    """The untied head: 100,663,296."""
    return config["hidden_size"] * config["vocab_size"]


def params(config: dict) -> int:
    """Every parameter the chip holds, each layer once: the layers, the
    embedding, the head, the final norm and the exit gate's weight and
    bias (612,438,017 for the file's 8 layers)."""
    d = config["hidden_size"]
    return (config["num_hidden_layers"] * layer_params(config)
            + config["vocab_size"] * d + head_params(config) + d + d + 1)


def matmul_params_per_token(config: dict) -> int:
    """Parameters one token's forward pass multiplies by, each as often
    as it is applied: the layers' matrices and the head, every pass (the
    exit gate's 2,048 a pass are left out)."""
    return passes(config) * (
        config["num_hidden_layers"] * layer_matmul_params(config)
        + head_params(config))


def causal_pairs(seq: int) -> int:
    """(query, key) pairs a causal mask allows in one sequence and head."""
    return seq * (seq + 1) // 2


def attention_forward_flops(config: dict, seq: int) -> int:
    """One layer application's QK^T and PV over the causal pairs, every
    head (274.9 GFLOP at 8,192)."""
    return (causal_pairs(seq) * 4 * config["head_dim"]
            * config["num_attention_heads"])


def attention_train_flops(config: dict, seq: int) -> int:
    """A step's attention work on one sequence: forward and backward of
    every layer application (3 x pairs x 4 x 128 x 16 x n x T)."""
    return (3 * attention_forward_flops(config, seq)
            * config["num_hidden_layers"] * passes(config))


def heads_train_flops(config: dict, seq: int) -> int:
    """A step's head work on one sequence: 3 x T x 2 d V L (the logits,
    their cotangent's two products; the logits computed again in the
    backward pass are in the time and not in the work)."""
    return 3 * passes(config) * 2 * head_params(config) * seq


def forward_flops_per_token(config: dict, seq: int) -> dict:
    """Forward FLOPs a token, by part, all passes."""
    d, t = config["hidden_size"], passes(config)
    layers = config["num_hidden_layers"]
    width = config["num_attention_heads"] * config["head_dim"]
    return {
        "projections": 2 * t * layers * 4 * d * width,
        "swiglu": 2 * t * layers * 3 * d * config["intermediate_size"],
        "heads": 2 * t * head_params(config),
        "attention": (t * layers * attention_forward_flops(config, seq)
                      / seq),
    }


def train(config: dict, data: dict) -> dict:
    seq = data["seq_len"]
    forward = (2 * matmul_params_per_token(config) * seq
               + config["num_hidden_layers"] * passes(config)
               * attention_forward_flops(config, seq))
    return {
        "flops_per_sample": 3 * forward,
        "min_bytes_per_step": (_optimizer_bytes(params(config), moments=2)
                               + data["batch"] * seq * 2 * 4),
    }
