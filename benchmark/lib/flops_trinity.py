"""Operations and bytes of a training step of the Trinity-Mini share in
``benchmark/configs/trinity-mini.json``, from the file's widths only --
never from what the program executes, so rematerialised forward passes
do not count. Same conventions as ``flops.py``: a multiply-add is 2
operations, backward costs twice the forward, lookups count 0.

The routed experts' term is an EXPECTATION: a token's
``num_experts_per_tok`` choices fall on the ``num_experts`` held here
with probability held / routed-over each, so 8 * 16 / 128 = 1 held
assignment a token. The load a run really sees is what the program
counts (``zoo_model_moe_assignments_held_total``); where it exceeds the
expectation ``train_mfu`` and ``train_step_roofline`` read high by the
excess times the experts' share of the step (101 of 738 MFLOP a token),
and ``train_moe_experts_roofline`` uses the counted assignments.
"""

from benchmark.lib.flops import _optimizer_bytes

SLIDING = "sliding_attention"


def _swiglu(d: int, width: int) -> int:
    return 3 * d * width


def attention_params(config: dict) -> int:
    """Wq, Wo, Wg at [d, heads * head_dim]; Wk, Wv over the KV heads."""
    d, hd = config["hidden_size"], config["head_dim"]
    return (3 * d * config["num_attention_heads"] * hd
            + 2 * d * config["num_key_value_heads"] * hd)


def expert_params(config: dict) -> int:
    """One routed expert's three matrices."""
    return _swiglu(config["hidden_size"], config["moe_intermediate_size"])


def _layer_norm_params(config: dict) -> int:
    return 4 * config["hidden_size"] + 2 * config["head_dim"]


def matmul_params_per_token(config: dict) -> int:
    """Parameters one token's forward pass multiplies by: everything
    outside the routed experts, the head, and the expected held
    assignments' experts."""
    d = config["hidden_size"]
    n_layers = config["num_hidden_layers"]
    held_per_token = (config["num_experts_per_tok"] * config["num_experts"]
                      / config["num_experts_routed_over"])
    per_expert_layer = (
        config["num_shared_experts"] * expert_params(config)
        + d * config["num_experts_routed_over"]
        + held_per_token * expert_params(config))
    return int(n_layers * attention_params(config)
               + config["num_dense_layers"] * _swiglu(
                   d, config["intermediate_size"])
               + expert_layers(config) * per_expert_layer
               + d * config["vocab_size"])


def params(config: dict) -> int:
    """Every parameter the chip holds (705,473,792 for the file)."""
    d = config["hidden_size"]
    n_layers = config["num_hidden_layers"]
    per_expert_layer = (
        (config["num_shared_experts"] + config["num_experts"])
        * expert_params(config) + d * config["num_experts_routed_over"])
    return (n_layers * (attention_params(config)
                        + _layer_norm_params(config))
            + config["num_dense_layers"] * _swiglu(
                d, config["intermediate_size"])
            + expert_layers(config) * per_expert_layer
            + 2 * d * config["vocab_size"] + d)


def attention_pairs(config: dict, seq: int, kind: str) -> int:
    """(query, key) pairs the masks allow in one sequence of one layer:
    causal, and on a sliding layer only the window's newest keys."""
    if kind != SLIDING or config["sliding_window"] >= seq:
        return seq * (seq + 1) // 2
    w = config["sliding_window"]
    return w * (w + 1) // 2 + (seq - w) * w


def attention_forward_flops(config: dict, seq: int, kind: str) -> int:
    """QK^T and PV over the allowed pairs, every query head."""
    return (attention_pairs(config, seq, kind) * 2 * 2 * config["head_dim"]
            * config["num_attention_heads"])


def layers_of(config: dict, kind: str) -> int:
    return sum(1 for t in config["layer_types"] if t == kind)


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def held_expert_weight_bytes(config: dict) -> int:
    """The held experts of every expert layer in bfloat16, the type the
    grouped products read."""
    return (expert_layers(config) * config["num_experts"]
            * expert_params(config) * 2)


def train(config: dict, data: dict) -> dict:
    seq = data["seq_len"]
    forward = 2 * matmul_params_per_token(config) * seq + sum(
        attention_forward_flops(config, seq, kind)
        for kind in config["layer_types"])
    return {
        "flops_per_sample": 3 * forward,
        "min_bytes_per_step": (_optimizer_bytes(params(config), moments=2)
                               + data["batch"] * seq * 2 * 4),
    }
