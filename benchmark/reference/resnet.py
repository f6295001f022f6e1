"""Plain reference: ResNet (He et al. 2015, arXiv:1512.03385) forward
pass in eval mode, float32 with ``default_matmul_precision("highest")``.

Bottleneck or basic blocks per the configuration file; the stride of a
down-sampling bottleneck sits on its 3x3 convolution (the "v1.5"
arrangement torchvision and the program use; the paper put it on the
first 1x1 -- the one departure, noted in the configuration). Batch
normalisation uses the running statistics. Images arrive as uint8 and
are scaled by the ImageNet channel means and deviations first, as the
program's classifier does on the device. Convolutions pad "SAME" as
XLA defines it. Only :func:`weights_from_program` knows the program's
parameter names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MEAN = np.asarray([0.485, 0.456, 0.406], np.float32)
STD = np.asarray([0.229, 0.224, 0.225], np.float32)
BN_EPS = 1e-5


def weights_from_program(variables: dict) -> dict:
    p = variables["params"]["backbone"]
    s = variables["batch_stats"]["backbone"]

    def conv(name, scope=p):
        return np.asarray(scope[name]["kernel"], np.float32)

    def bn(name, scope_p, scope_s):
        return tuple(np.asarray(a, np.float32) for a in (
            scope_p[name]["scale"], scope_p[name]["bias"],
            scope_s[name]["mean"], scope_s[name]["var"]))

    blocks = {}
    for key in p:
        if not key.startswith("stage"):
            continue
        bp, bs = p[key], s[key]
        blk = {}
        for n in ("conv1", "conv2", "conv3", "proj_conv"):
            if n in bp:
                blk[n] = conv(n, bp)
        for n in ("bn1", "bn2", "bn3", "proj_bn"):
            if n in bp:
                blk[n] = bn(n, bp, bs)
        blocks[key] = blk
    return {"stem_conv": conv("stem_conv"), "stem_bn": bn("stem_bn", p, s),
            "blocks": blocks,
            "head": (np.asarray(p["head"]["kernel"], np.float32),
                     np.asarray(p["head"]["bias"], np.float32))}


def _conv(x, w, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, stats):
    scale, bias, mean, var = stats
    return (x - mean) / jnp.sqrt(var + BN_EPS) * scale + bias


def _block(x, blk, stride):
    if "conv3" in blk:      # bottleneck: 1x1, 3x3 (strided), 1x1
        y = jax.nn.relu(_bn(_conv(x, blk["conv1"]), blk["bn1"]))
        y = jax.nn.relu(_bn(_conv(y, blk["conv2"], stride), blk["bn2"]))
        y = _bn(_conv(y, blk["conv3"]), blk["bn3"])
    else:                   # basic: 3x3 (strided), 3x3
        y = jax.nn.relu(_bn(_conv(x, blk["conv1"], stride), blk["bn1"]))
        y = _bn(_conv(y, blk["conv2"]), blk["bn2"])
    if "proj_conv" in blk:
        x = _bn(_conv(x, blk["proj_conv"], stride), blk["proj_bn"])
    return jax.nn.relu(x + y)


def forward(variables: dict, images, config: dict):
    """Class logits [B, num_classes] float32 for uint8 images."""
    weights = weights_from_program(variables)
    stage_sizes = [int(n) for n in config["stage_sizes"]]

    @jax.jit
    def run(weights, images):
        with jax.default_matmul_precision("highest"):
            x = (images.astype(jnp.float32) / 255.0 - MEAN) / STD
            x = _conv(x, weights["stem_conv"], 2, [(3, 3), (3, 3)])
            x = jax.nn.relu(_bn(x, weights["stem_bn"]))
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
            for i, n_blocks in enumerate(stage_sizes):
                for j in range(n_blocks):
                    stride = 2 if (i > 0 and j == 0) else 1
                    x = _block(x, weights["blocks"][f"stage{i}_block{j}"],
                               stride)
            x = x.mean(axis=(1, 2))
            return x @ weights["head"][0] + weights["head"][1]

    return jax.device_get(run(weights, jnp.asarray(images)))
