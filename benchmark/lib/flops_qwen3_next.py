"""Operations and bytes of a training step of the Qwen3-Next-80B-A3B
stage in ``benchmark/configs/qwen3-next-80b-a3b.json``, from the file's
widths only -- never from what the program executes, so rematerialised
forward passes, padding and repeated heads do not count. Same
conventions as ``flops.py`` and ``flops_moonlight.py``: a multiply-add
is 2 operations, backward costs twice the forward, lookups and
elementwise work count 0.

The routed experts' term is an EXPECTATION: a token's 10 choices fall
on the 32 experts held here with probability 32 / 512 each, so 0.625
held assignment a token and layer; the load a run sees is what the
program counts (``zoo_model_moe_assignments_held_total``,
``moe_held_load_over_expected``).

The delta rule's work is its chunked form's products (the WY / UT
algorithm of arXiv:2412.06464, chunks of ``delta_rule_chunk``), per
chunk of C positions and value head: K K^T and Q K^T (C^2 d_k each),
the triangular inverse (C^3 / 6 multiply-adds), W and U (C^2 d_k,
C^2 d_v), W S, Q S and K^T V' (C d_k d_v each) and the intra-chunk
product with V' (C^2 d_v). Its least bytes are q, k, v, g and beta
read and o written once forward, and backward the same inputs and
``do`` read, their gradients written and every chunk's float32 state
read once: what any implementation of the step must move.
"""

from benchmark.lib.flops import _optimizer_bytes

BF16, F32 = 2, 4


def _swiglu(d: int, width: int) -> int:
    return 3 * d * width


def layer_counts(config: dict) -> tuple:
    """(Gated DeltaNet layers, attention layers)."""
    n = config["num_hidden_layers"]
    full = n // config["full_attention_interval"]
    return n - full, full


def linear_params(config: dict) -> int:
    """One Gated DeltaNet mixer: in_proj_qkvz, in_proj_ba, the
    convolution, A_log and dt_bias, the gated norm, out_proj
    (33,718,464 at the published widths)."""
    d = config["hidden_size"]
    key = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    value = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    heads = config["linear_num_value_heads"]
    return (d * (2 * key + 2 * value) + d * 2 * heads
            + config["linear_conv_kernel_dim"] * (2 * key + value)
            + 2 * heads + config["linear_value_head_dim"] + value * d)


def linear_matmul_params(config: dict) -> int:
    """What a token multiplies by in one Gated DeltaNet mixer."""
    d = config["hidden_size"]
    key = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    value = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    return (d * (2 * key + 2 * value) + d * 2 * config[
        "linear_num_value_heads"] + value * d)


def attention_matmul_params(config: dict) -> int:
    """q, gate, k, v and o of one gated attention mixer."""
    d, dim = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2 * d * heads * dim + 2 * d * kv * dim + heads * dim * d


def attention_params(config: dict) -> int:
    """27,263,488 at the published widths, the q / k norms included."""
    return attention_matmul_params(config) + 2 * config["head_dim"]


def expert_params(config: dict) -> int:
    """One routed expert's three matrices."""
    return _swiglu(config["hidden_size"], config["moe_intermediate_size"])


def moe_params(config: dict) -> int:
    """One layer's share: router, held experts, shared expert, its gate
    (104,859,648 at the published widths)."""
    d = config["hidden_size"]
    return (d * config["num_experts_routed_over"]
            + config["num_experts"] * expert_params(config)
            + _swiglu(d, config["shared_expert_intermediate_size"]) + d)


def held_per_token(config: dict) -> float:
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["num_experts_routed_over"])


def params(config: dict) -> int:
    """Every parameter the chip holds (625,667,136 for the file)."""
    d = config["hidden_size"]
    linear, full = layer_counts(config)
    return (linear * linear_params(config) + full * attention_params(config)
            + config["num_hidden_layers"] * (moe_params(config) + 2 * d)
            + 2 * d * config["vocab_size"] + d)


def matmul_params_per_token(config: dict) -> float:
    """Parameters one token's forward pass multiplies by: the mixers'
    projections, router, shared expert and its gate, the expected held
    assignments' experts, the head."""
    d = config["hidden_size"]
    linear, full = layer_counts(config)
    per_moe = (d * config["num_experts_routed_over"]
               + _swiglu(d, config["shared_expert_intermediate_size"]) + d
               + held_per_token(config) * expert_params(config))
    return (linear * linear_matmul_params(config)
            + full * attention_matmul_params(config)
            + config["num_hidden_layers"] * per_moe
            + d * config["vocab_size"])


def attention_pairs(seq: int) -> int:
    """(query, key) pairs the causal mask allows in one sequence."""
    return seq * (seq + 1) // 2


def attention_forward_flops(config: dict, seq: int) -> int:
    """One layer's QK^T and PV over the allowed pairs, every query head
    (67.1 MFLOP a token at 8k): the published work."""
    return (attention_pairs(seq) * 2 * 2 * config["head_dim"]
            * config["num_attention_heads"])


def delta_rule_forward_flops(config: dict, seq: int) -> int:
    """One Gated DeltaNet layer's chunked delta rule over a sequence
    (module docstring)."""
    c = config["delta_rule_chunk"]
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    per_chunk = 2 * (2 * c * c * d_k + c ** 3 // 6 + c * c * (d_k + d_v)
                     + 3 * c * d_k * d_v + c * c * d_v)
    return per_chunk * (seq // c) * config["linear_num_value_heads"]


def delta_rule_train_min_bytes(config: dict, seq: int) -> int:
    """One Gated DeltaNet layer's delta rule, forward and backward, over
    a sequence (module docstring): bfloat16 q, k, v and o, float32 g,
    beta and the chunk states."""
    h = config["linear_num_value_heads"]
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    inputs = seq * h * (BF16 * (2 * d_k + d_v) + 2 * F32)
    out = seq * h * BF16 * d_v
    states = (seq // config["delta_rule_chunk"]) * h * d_k * d_v * F32
    # forward: inputs in, o out; backward: inputs and do in, states
    # read, the inputs' gradients out
    return (inputs + out) + (inputs + out + states + inputs)


def train(config: dict, data: dict) -> dict:
    seq = data["seq_len"]
    linear, full = layer_counts(config)
    forward = int(2 * matmul_params_per_token(config) * seq
                  + full * attention_forward_flops(config, seq)
                  + linear * delta_rule_forward_flops(config, seq))
    return {
        "flops_per_sample": 3 * forward,
        "min_bytes_per_step": (_optimizer_bytes(params(config), moments=2)
                               + data["batch"] * seq * 2 * 4),
    }
