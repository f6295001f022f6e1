"""Operations and bytes that a training step needs, from the published
widths in a configuration file and the shapes of a batch -- never from
what the program happens to execute, so recomputation does not count.

A configuration names its function by dotted path (``"flops"``); a new
architecture brings a new file with a function of the same signature:
``fn(config, data) -> {"flops_per_sample", "min_bytes_per_step"}``.
``data`` is the traffic file's ``data`` group (batch, seq_len, ...).

Conventions: a multiply-add is 2 operations; backward costs twice the
forward (so training is 3 x forward); embedding lookups are gathers and
count 0. ``min_bytes_per_step`` is a lower bound on HBM traffic: every
parameter read once for the forward and once for the backward pass in
its stored type, its gradient written and read once, the optimizer's
moments read and written once, the parameter written once, plus the
batch itself. Activation traffic is left out (it depends on fusion and
recomputation choices), so a step that is bound by activation bytes
shows as a low share of a compute roofline."""

F32 = 4


def bert_layer_forward_flops(hidden: int, ffn: int, seq: int) -> int:
    """One encoder layer, one sequence: QKV + output projections
    (4 matmuls of [L,H]x[H,H]), scores and context ([L,L] per head, all
    heads together L*L*H each), and the two FFN matmuls."""
    proj = 4 * 2 * seq * hidden * hidden
    attn = 2 * 2 * seq * seq * hidden
    mlp = 2 * 2 * seq * hidden * ffn
    return proj + attn + mlp


def bert_dense_params(config: dict) -> int:
    """Matmul parameters with biases and LayerNorms, without the
    embedding tables; the span head is [H, 2]."""
    h, f = config["hidden_size"], config["intermediate_size"]
    per_layer = (4 * h * h + 4 * h) + (2 * h * f + f + h) + 4 * h
    return config["num_hidden_layers"] * per_layer + 2 * h + 2


def bert_params(config: dict) -> int:
    h = config["hidden_size"]
    embed = (config["vocab_size"] + config["max_position_embeddings"]
             + config["type_vocab_size"]) * h + 2 * h
    pooler = h * h + h
    return embed + pooler + bert_dense_params(config)


def _optimizer_bytes(n_params: int, moments: int) -> int:
    # p read fwd + bwd, grad written + read, p written, moments r + w
    return n_params * F32 * (5 + 2 * moments)


def bert_train(config: dict, data: dict) -> dict:
    seq = data["seq_len"]
    h = config["hidden_size"]
    forward = (config["num_hidden_layers"] * bert_layer_forward_flops(
        h, config["intermediate_size"], seq) + 2 * seq * h * 2)
    # the type-embedding table and the pooler exist in the model but the
    # span task with ids only reads neither: they get no update traffic
    trained = bert_dense_params(config) + (
        config["vocab_size"] + config["max_position_embeddings"]) * h + 2 * h
    return {
        "flops_per_sample": 3 * forward,
        "min_bytes_per_step": (_optimizer_bytes(trained, moments=2)
                               + data["batch"] * seq * 4),
    }


def conv_flops(h_out: int, w_out: int, c_in: int, c_out: int, k: int) -> int:
    return 2 * h_out * w_out * c_in * c_out * k * k


def resnet_block_forward_flops(block: str, size_in: int, c_in: int,
                               width: int, stride: int,
                               projection: bool) -> int:
    """One residual block on one image; ``size_in`` is the input's side,
    the stride sits on the 3x3 convolution (v1.5) or the first 3x3 of a
    basic block. Only convolutions are counted (BN/ReLU/add are bytes)."""
    size_out = size_in // stride
    if block == "bottleneck":
        c_out = 4 * width
        total = (conv_flops(size_in, size_in, c_in, width, 1)
                 + conv_flops(size_out, size_out, width, width, 3)
                 + conv_flops(size_out, size_out, width, c_out, 1))
    elif block == "basic":
        c_out = width
        total = (conv_flops(size_out, size_out, c_in, width, 3)
                 + conv_flops(size_out, size_out, width, width, 3))
    else:
        raise ValueError(f"unknown residual block kind {block!r}")
    if projection:
        total += conv_flops(size_out, size_out, c_in, c_out, 1)
    return total


def _resnet_blocks(config: dict):
    """(input side, channels in, width, channels out, stride, first of
    its stage) for every residual block, in order; the input is what
    the 7x7/2 stem and the 3x3/2 max pool leave."""
    size = config["image_size"] // 4
    c_in = config["stem_width"]
    expansion = 4 if config["block"] == "bottleneck" else 1
    for i, (n_blocks, width) in enumerate(zip(config["stage_sizes"],
                                              config["stage_widths"])):
        for j in range(n_blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            yield size, c_in, width, width * expansion, stride, j == 0
            size //= stride
            c_in = width * expansion


def resnet_forward_flops(config: dict) -> int:
    stem_side = config["image_size"] // 2
    total = conv_flops(stem_side, stem_side, 3, config["stem_width"], 7)
    for size, c_in, width, c_out, stride, first in _resnet_blocks(config):
        total += resnet_block_forward_flops(
            config["block"], size, c_in, width, stride, projection=first)
    return total + 2 * c_out * config["num_classes"]


def resnet_params(config: dict) -> int:
    """Convolution kernels, BN scale+bias, and the classifier."""
    def conv(c_in, c_out, k):
        return c_in * c_out * k * k + 2 * c_out

    total = conv(3, config["stem_width"], 7)
    for _, c_in, width, c_out, _, first in _resnet_blocks(config):
        if config["block"] == "bottleneck":
            total += (conv(c_in, width, 1) + conv(width, width, 3)
                      + conv(width, c_out, 1))
        else:
            total += conv(c_in, width, 3) + conv(width, width, 3)
        if first:
            total += conv(c_in, c_out, 1)
    return total + c_out * config["num_classes"] + config["num_classes"]


def resnet_train(config: dict, data: dict) -> dict:
    image_bytes = data["image_size"] ** 2 * 3    # uint8 from the host
    return {
        "flops_per_sample": 3 * resnet_forward_flops(config),
        "min_bytes_per_step": (
            _optimizer_bytes(resnet_params(config),
                             moments=config["optimizer_moments"])
            + data["batch"] * image_bytes),
    }
