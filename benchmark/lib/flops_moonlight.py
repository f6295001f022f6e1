"""Operations and bytes of a training step of the Moonlight-16B-A3B
share in ``benchmark/configs/moonlight-16b-a3b.json``, from the file's
widths only -- never from what the program executes, so rematerialised
forward passes, padding and repeated heads do not count. Same
conventions as ``flops.py`` and ``flops_trinity.py``: a multiply-add is
2 operations, backward costs twice the forward, lookups count 0.

The routed experts' term is an EXPECTATION: a token's
``num_experts_per_tok`` choices fall on the ``n_routed_experts`` held
here with probability held / routed-over each, so 6 * 8 / 64 = 0.75
held assignment a token and expert layer. The load a run really sees is
what the program counts (``zoo_model_moe_assignments_held_total``);
where it exceeds the expectation ``train_mfu`` and
``train_step_roofline`` read high by the excess times the routed
experts' share of the step (13 of 176 MFLOP a token and expert layer).
"""

from benchmark.lib.flops import _optimizer_bytes


def _swiglu(d: int, width: int) -> int:
    return 3 * d * width


def attention_params(config: dict) -> int:
    """Wq [d, heads * (nope + rot)], Wkva [d, latent + rot], Wkvb
    [latent, heads * (nope + v)], Wo [heads * v, d]: 13,762,560 at the
    published widths (the latent norm's 512 are counted with the
    norms)."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rot = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    latent, v = config["kv_lora_rank"], config["v_head_dim"]
    return (d * heads * (nope + rot) + d * (latent + rot)
            + latent * heads * (nope + v) + heads * v * d)


def expert_params(config: dict) -> int:
    """One routed expert's three matrices."""
    return _swiglu(config["hidden_size"], config["moe_intermediate_size"])


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def _layer_norm_params(config: dict) -> int:
    return 2 * config["hidden_size"] + config["kv_lora_rank"]


def matmul_params_per_token(config: dict) -> float:
    """Parameters one token's forward pass multiplies by: everything
    outside the routed experts, the head, and the expected held
    assignments' experts."""
    d = config["hidden_size"]
    held_per_token = (config["num_experts_per_tok"]
                      * config["n_routed_experts"]
                      / config["n_routed_experts_routed_over"])
    per_expert_layer = (
        config["n_shared_experts"] * expert_params(config)
        + d * config["n_routed_experts_routed_over"]
        + held_per_token * expert_params(config))
    return (config["num_hidden_layers"] * attention_params(config)
            + config["first_k_dense_replace"] * _swiglu(
                d, config["intermediate_size"])
            + expert_layers(config) * per_expert_layer
            + d * config["vocab_size"])


def params(config: dict) -> int:
    """Every parameter the chip holds (668,890,112 for the file)."""
    d = config["hidden_size"]
    per_expert_layer = (
        (config["n_shared_experts"] + config["n_routed_experts"])
        * expert_params(config) + d * config["n_routed_experts_routed_over"])
    return (config["num_hidden_layers"] * (attention_params(config)
                                           + _layer_norm_params(config))
            + config["first_k_dense_replace"] * _swiglu(
                d, config["intermediate_size"])
            + expert_layers(config) * per_expert_layer
            + 2 * d * config["vocab_size"] + d)


def attention_pairs(seq: int) -> int:
    """(query, key) pairs the causal mask allows in one sequence."""
    return seq * (seq + 1) // 2


def attention_forward_flops(config: dict, seq: int) -> int:
    """One layer's QK^T over ``nope + rot`` and PV over ``v`` columns,
    the allowed pairs, every head: the published work, whatever the
    kernel pads or repeats."""
    return (attention_pairs(seq) * 2
            * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
               + config["v_head_dim"]) * config["num_attention_heads"])


def train(config: dict, data: dict) -> dict:
    seq = data["seq_len"]
    forward = int(2 * matmul_params_per_token(config) * seq
                  + config["num_hidden_layers"]
                  * attention_forward_flops(config, seq))
    return {
        "flops_per_sample": 3 * forward,
        "min_bytes_per_step": (_optimizer_bytes(params(config), moments=2)
                               + data["batch"] * seq * 2 * 4),
    }
