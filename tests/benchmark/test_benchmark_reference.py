"""The plain references against the program's forward at tiny widths on
the CPU, and the FLOPs/bytes functions against hand counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import flops
from benchmark.reference import bert as ref_bert
from benchmark.reference import resnet as ref_resnet

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _tolerance(config_name):
    with open(os.path.join(REPO, "benchmark", "configs",
                           config_name + ".json")) as f:
        return json.load(f)["reference"]["tolerance"]


BERT_BASE = dict(hidden_size=768, intermediate_size=3072,
                 num_hidden_layers=12, num_attention_heads=12,
                 vocab_size=30522, max_position_embeddings=512,
                 type_vocab_size=2)
RESNET50 = dict(image_size=224, stem_width=64, block="bottleneck",
                stage_sizes=[3, 4, 6, 3], stage_widths=[64, 128, 256, 512],
                num_classes=1000, optimizer_moments=1)


def _rel_err(got, want):
    got = np.concatenate([np.asarray(g, np.float32).ravel()
                          for g in jax.tree_util.tree_leaves(got)])
    want = np.concatenate([np.asarray(w, np.float32).ravel()
                           for w in jax.tree_util.tree_leaves(want)])
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _randomized(variables, seed):
    """Init leaves some terms at exactly 0 or 1 (biases, BN scales and
    statistics); give every leaf a value, so a dropped term shows."""
    leaves, tree = jax.tree_util.tree_flatten(variables)
    rng = np.random.default_rng(seed)
    out = []
    for leaf in leaves:
        a = np.asarray(leaf, np.float32)
        if a.ndim == 1:
            a = a + rng.uniform(0.2, 0.8, a.shape).astype(np.float32)
        out.append(a)
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module")
def tiny_bert():
    from analytics_zoo_tpu.models.text.bert_squad import BERTForSQuAD

    config = {"num_attention_heads": 2, "layer_norm_eps": 1e-12}
    ids = np.random.default_rng(0).integers(0, 96, (3, 24), dtype=np.int32)

    def build(dtype):
        module = BERTForSQuAD(vocab=96, hidden_size=32, n_block=2, n_head=2,
                              intermediate_size=64, max_position_len=48,
                              dtype=dtype)
        variables = module.init(jax.random.PRNGKey(1), {"input_ids": ids})
        return module, _randomized(variables, 2)

    return config, ids, build


@pytest.mark.parametrize("dtype,tolerance", [
    # float32 against float32: only summation order differs
    (jnp.float32, 2e-5),
    # the served type: inside the configuration's tolerance
    (jnp.bfloat16, None),
])
def test_bert_reference_agrees_with_the_program(tiny_bert, dtype, tolerance):
    tolerance = tolerance or _tolerance("bert-base-squad")
    config, ids, build = tiny_bert
    module, variables = build(dtype)
    got = module.apply(variables, {"input_ids": ids}, train=False)
    want = ref_bert.forward(variables, {"input_ids": ids}, config)
    assert got[0].shape == want[0].shape == (3, 24)
    assert _rel_err(got, want) <= tolerance


@pytest.mark.parametrize("dropped", [
    ("squad", "bert", "encoder_1", "ffn_out", "bias"),
    ("squad", "bert", "encoder_0", "ln_attn", "scale"),
    ("squad", "bert", "position_embed"),
])
def test_bert_tolerance_catches_a_dropped_term(tiny_bert, dropped):
    config, ids, build = tiny_bert
    module, variables = build(jnp.float32)
    got = module.apply(variables, {"input_ids": ids}, train=False)
    broken = jax.tree_util.tree_map(lambda a: a, variables)
    node = broken["params"]
    for key in dropped[:-1]:
        node = node[key]
    node[dropped[-1]] = np.zeros_like(node[dropped[-1]])
    want = ref_bert.forward(broken, {"input_ids": ids}, config)
    assert _rel_err(got, want) > _tolerance("bert-base-squad")


@pytest.mark.parametrize("block,stages,dtype,tolerance", [
    ("bottleneck", (1, 2), jnp.float32, 5e-5),
    ("basic", (2, 1), jnp.float32, 5e-5),
    ("bottleneck", (1, 2), jnp.bfloat16, None),
])
def test_resnet_reference_agrees_with_the_program(block, stages, dtype,
                                                  tolerance):
    tolerance = tolerance or _tolerance("resnet50-imagenet")
    from analytics_zoo_tpu.models.image.classifier import _NormalizedBackbone
    from analytics_zoo_tpu.models.image.resnet import (BasicBlock,
                                                       BottleneckBlock,
                                                       ResNet)

    images = np.random.default_rng(3).integers(
        0, 256, (2, 32, 32, 3), dtype=np.uint8)
    module = _NormalizedBackbone(backbone=ResNet(
        stage_sizes=stages, num_classes=7, num_filters=8, dtype=dtype,
        block=BottleneckBlock if block == "bottleneck" else BasicBlock))
    variables = _randomized(module.init(jax.random.PRNGKey(4), images), 5)
    got = module.apply(variables, images, train=False)
    want = ref_resnet.forward(variables, images,
                              {"stage_sizes": list(stages)})
    assert got.shape == want.shape == (2, 7)
    assert _rel_err(got, want) <= tolerance


def test_bert_counts_match_hand_counts():
    # one layer, one sequence of 384: 4 projections [384,768]x[768,768],
    # scores + context 2 x [384,384] x 768, FFN [384,768]x[768,3072] x 2
    proj = 4 * 2 * 384 * 768 * 768
    attn = 2 * 2 * 384 * 384 * 768
    mlp = 2 * 2 * 384 * 768 * 3072
    assert (proj, attn, mlp) == (1811939328, 452984832, 3623878656)
    assert flops.bert_layer_forward_flops(768, 3072, 384) == 5888802816
    # published size: 109,482,240 parameters + the [768, 2] span head
    assert flops.bert_params(BERT_BASE) == 109482240 + 768 * 2 + 2
    work = flops.bert_train(BERT_BASE, {"seq_len": 384, "batch": 32})
    head = 2 * 384 * 768 * 2
    assert work["flops_per_sample"] == 3 * (12 * 5888802816 + head)
    # the usual estimate: 6 x dense parameters + 12 x layers x H x L a token
    per_token = work["flops_per_sample"] / 384
    estimate = 6 * flops.bert_dense_params(BERT_BASE) + 12 * 12 * 768 * 384
    assert per_token == pytest.approx(estimate, rel=2e-3)
    trained = 109482240 + 1538 - 2 * 768 - (768 * 768 + 768)
    assert work["min_bytes_per_step"] == trained * 4 * 9 + 32 * 384 * 4


def test_resnet_counts_match_hand_counts():
    # first bottleneck (56x56, 64 -> 64 -> 64 -> 256, with projection)
    conv1 = 2 * 56 * 56 * 64 * 64
    conv2 = 2 * 56 * 56 * 64 * 64 * 9
    conv3 = 2 * 56 * 56 * 64 * 256
    proj = 2 * 56 * 56 * 64 * 256
    assert conv1 + conv2 + conv3 + proj == 462422016
    assert flops.resnet_block_forward_flops(
        "bottleneck", 56, 64, 64, 1, projection=True) == 462422016
    # a down-sampling basic block (56 -> 28, 64 -> 128, with projection)
    basic = (2 * 28 * 28 * 64 * 128 * 9 + 2 * 28 * 28 * 128 * 128 * 9
             + 2 * 28 * 28 * 64 * 128)
    assert flops.resnet_block_forward_flops(
        "basic", 56, 64, 128, 2, projection=True) == basic
    # published: 25,557,032 parameters, 4.09 GMAC forward at 224
    assert flops.resnet_params(RESNET50) == 25557032
    assert flops.resnet_forward_flops(RESNET50) / 2 == pytest.approx(
        4.09e9, rel=2e-3)
    work = flops.resnet_train(RESNET50, {"batch": 256, "image_size": 224})
    assert work["flops_per_sample"] == 3 * flops.resnet_forward_flops(RESNET50)
    assert work["min_bytes_per_step"] == (25557032 * 4 * 7
                                          + 256 * 224 * 224 * 3)
