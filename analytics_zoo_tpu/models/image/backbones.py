"""Classic CNN backbones: Inception-v1 (GoogLeNet), MobileNet v1, VGG-16.

The reference's image-classification zoo spans these families as
pretrained load-and-predict models (ref: pyzoo/zoo/models/image/
imageclassification/image_classifier.py -- Inception-v1/MobileNet/VGG/
DenseNet variants listed in the model-zoo table) and ships Inception-v1
as its flagship distributed-training example (ref: zoo/src/main/scala/
com/intel/analytics/zoo/examples/inception/Train.scala /
Inception.scala). Here each is a trainable flax module, channels-last,
bf16-friendly, exposed through ``ImageClassifier``.

Design notes (TPU): all three are plain conv stacks XLA maps straight
onto the MXU; batch-norm everywhere (including the VGG variant, the
standard modern recipe) keeps activations bf16-stable; MobileNet's
depthwise convs use ``feature_group_count`` so XLA emits the fused
depthwise kernel.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from analytics_zoo_tpu.keras.layers.normalization import (
    batch_norm as _norm)
from analytics_zoo_tpu.ops.dropout import Dropout


class InceptionBlock(nn.Module):
    """One GoogLeNet mixed block: 1x1 | 1x1-3x3 | 1x1-5x5 | pool-1x1
    branches concatenated on channels (ref: Inception.scala's
    inceptionLayerV1 branch structure)."""

    b1: int          # 1x1 branch filters
    b3_reduce: int   # 3x3 branch bottleneck
    b3: int
    b5_reduce: int   # 5x5 branch bottleneck
    b5: int
    pool_proj: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = _norm(train, self.dtype)
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)

        def unit(h, filters, kernel, name):
            h = conv(filters, kernel, name=f"{name}_conv")(h)
            return nn.relu(norm(name=f"{name}_bn")(h))

        br1 = unit(x, self.b1, (1, 1), "b1")
        br3 = unit(x, self.b3_reduce, (1, 1), "b3r")
        br3 = unit(br3, self.b3, (3, 3), "b3")
        br5 = unit(x, self.b5_reduce, (1, 1), "b5r")
        br5 = unit(br5, self.b5, (5, 5), "b5")
        brp = nn.max_pool(x, (3, 3), strides=(1, 1), padding="SAME")
        brp = unit(brp, self.pool_proj, (1, 1), "bp")
        return jnp.concatenate([br1, br3, br5, brp], axis=-1)


# GoogLeNet table: (b1, b3_reduce, b3, b5_reduce, b5, pool_proj)
_INCEPTION_CFG = {
    "3a": (64, 96, 128, 16, 32, 32),
    "3b": (128, 128, 192, 32, 96, 64),
    "4a": (192, 96, 208, 16, 48, 64),
    "4b": (160, 112, 224, 24, 64, 64),
    "4c": (128, 128, 256, 24, 64, 64),
    "4d": (112, 144, 288, 32, 64, 64),
    "4e": (256, 160, 320, 32, 128, 128),
    "5a": (256, 160, 320, 32, 128, 128),
    "5b": (384, 192, 384, 48, 128, 128),
}


class InceptionV1(nn.Module):
    """GoogLeNet with batch-norm (the reference's distributed-training
    flagship; ref: examples/inception/Inception.scala Inception_v1).
    The train-time auxiliary heads are omitted -- they existed to aid
    pre-BN optimization and modern BN training does not need them."""

    num_classes: int = 1000
    dropout_rate: float = 0.4
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = _norm(train, self.dtype)
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        x = conv(64, (7, 7), (2, 2), name="stem_conv1")(x)
        x = nn.relu(norm(name="stem_bn1")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        x = conv(64, (1, 1), name="stem_conv2")(x)
        x = nn.relu(norm(name="stem_bn2")(x))
        x = conv(192, (3, 3), name="stem_conv3")(x)
        x = nn.relu(norm(name="stem_bn3")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for key in ("3a", "3b"):
            x = InceptionBlock(*_INCEPTION_CFG[key], dtype=self.dtype,
                               name=f"mixed{key}")(x, train=train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for key in ("4a", "4b", "4c", "4d", "4e"):
            x = InceptionBlock(*_INCEPTION_CFG[key], dtype=self.dtype,
                               name=f"mixed{key}")(x, train=train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for key in ("5a", "5b"):
            x = InceptionBlock(*_INCEPTION_CFG[key], dtype=self.dtype,
                               name=f"mixed{key}")(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        x = Dropout(self.dropout_rate, deterministic=not train)(x)
        x = x.astype(jnp.float32)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="head")(x)


class _SeparableBlock(nn.Module):
    """Depthwise 3x3 + pointwise 1x1, each BN-relu (MobileNet v1 unit)."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = _norm(train, self.dtype)
        channels = x.shape[-1]
        x = nn.Conv(channels, (3, 3), self.strides, use_bias=False,
                    feature_group_count=channels, dtype=self.dtype,
                    name="dw_conv")(x)
        x = nn.relu(norm(name="dw_bn")(x))
        x = nn.Conv(self.filters, (1, 1), use_bias=False,
                    dtype=self.dtype, name="pw_conv")(x)
        return nn.relu(norm(name="pw_bn")(x))


class MobileNetV1(nn.Module):
    """MobileNet v1 with a width multiplier (ref model-zoo family:
    image_classifier.py "mobilenet" variants)."""

    num_classes: int = 1000
    width: float = 1.0
    dropout_rate: float = 0.001
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        def w(f):
            return max(8, int(f * self.width))

        norm = _norm(train, self.dtype)
        x = nn.Conv(w(32), (3, 3), (2, 2), use_bias=False,
                    dtype=self.dtype, name="stem_conv")(x)
        x = nn.relu(norm(name="stem_bn")(x))
        plan = [(64, 1), (128, 2), (128, 1), (256, 2), (256, 1),
                (512, 2)] + [(512, 1)] * 5 + [(1024, 2), (1024, 1)]
        for i, (filters, stride) in enumerate(plan):
            x = _SeparableBlock(w(filters), (stride, stride),
                                dtype=self.dtype,
                                name=f"block{i + 1}")(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        x = Dropout(self.dropout_rate, deterministic=not train)(x)
        x = x.astype(jnp.float32)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="head")(x)


class VGG16(nn.Module):
    """VGG-16 (configuration D) with batch-norm (ref model-zoo family:
    image_classifier.py "vgg-16"). The giant fc6/fc7 dense layers are
    kept at 4096 to match the family's capacity."""

    num_classes: int = 1000
    dropout_rate: float = 0.5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = _norm(train, self.dtype)
        plan = [(64, 2), (128, 2), (256, 3), (512, 3), (512, 3)]
        for s, (filters, reps) in enumerate(plan):
            for r in range(reps):
                x = nn.Conv(filters, (3, 3), use_bias=False,
                            dtype=self.dtype,
                            name=f"conv{s + 1}_{r + 1}")(x)
                x = nn.relu(norm(name=f"bn{s + 1}_{r + 1}")(x))
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        for i in (6, 7):
            x = nn.Dense(4096, dtype=self.dtype, name=f"fc{i}")(x)
            x = nn.relu(x)
            x = Dropout(self.dropout_rate, deterministic=not train)(x)
        x = x.astype(jnp.float32)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="head")(x)


class VGG19(VGG16):
    """VGG-19 (configuration E): the 16-layer plan with the last three
    stages deepened to four convs (ref model-zoo family:
    image_classifier.py "vgg-19")."""

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = _norm(train, self.dtype)
        plan = [(64, 2), (128, 2), (256, 4), (512, 4), (512, 4)]
        for s, (filters, reps) in enumerate(plan):
            for r in range(reps):
                x = nn.Conv(filters, (3, 3), use_bias=False,
                            dtype=self.dtype,
                            name=f"conv{s + 1}_{r + 1}")(x)
                x = nn.relu(norm(name=f"bn{s + 1}_{r + 1}")(x))
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        for i in (6, 7):
            x = nn.Dense(4096, dtype=self.dtype, name=f"fc{i}")(x)
            x = nn.relu(x)
            x = Dropout(self.dropout_rate, deterministic=not train)(x)
        x = x.astype(jnp.float32)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="head")(x)


class AlexNet(nn.Module):
    """AlexNet with batch-norm in place of LRN (ref model-zoo family:
    image_classifier.py "alexnet"; BN is the modern stand-in for the
    original local response normalization)."""

    num_classes: int = 1000
    dropout_rate: float = 0.5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = _norm(train, self.dtype)
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        x = conv(96, (11, 11), (4, 4), name="conv1")(x)
        x = nn.relu(norm(name="bn1")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        x = conv(256, (5, 5), name="conv2")(x)
        x = nn.relu(norm(name="bn2")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        x = conv(384, (3, 3), name="conv3")(x)
        x = nn.relu(norm(name="bn3")(x))
        x = conv(384, (3, 3), name="conv4")(x)
        x = nn.relu(norm(name="bn4")(x))
        x = conv(256, (3, 3), name="conv5")(x)
        x = nn.relu(norm(name="bn5")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        for i in (6, 7):
            x = nn.Dense(4096, dtype=self.dtype, name=f"fc{i}")(x)
            x = nn.relu(x)
            x = Dropout(self.dropout_rate, deterministic=not train)(x)
        x = x.astype(jnp.float32)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="head")(x)


class _FireModule(nn.Module):
    """SqueezeNet fire module: 1x1 squeeze, then parallel 1x1 + 3x3
    expands concatenated on channels."""

    squeeze: int
    expand: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = _norm(train, self.dtype)
        s = nn.Conv(self.squeeze, (1, 1), use_bias=False,
                    dtype=self.dtype, name="squeeze")(x)
        s = nn.relu(norm(name="squeeze_bn")(s))
        e1 = nn.relu(nn.Conv(self.expand, (1, 1), dtype=self.dtype,
                             name="expand1")(s))
        e3 = nn.relu(nn.Conv(self.expand, (3, 3), dtype=self.dtype,
                             name="expand3")(s))
        return jnp.concatenate([e1, e3], axis=-1)


class SqueezeNet(nn.Module):
    """SqueezeNet v1.1 (ref model-zoo family: image_classifier.py
    "squeezenet"): fire modules + a conv classifier head over global
    average pooling."""

    num_classes: int = 1000
    dropout_rate: float = 0.5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = _norm(train, self.dtype)
        x = nn.Conv(64, (3, 3), (2, 2), use_bias=False,
                    dtype=self.dtype, name="stem")(x)
        x = nn.relu(norm(name="stem_bn")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        # v1.1 schedule: pool after fire3 and fire5 (early pooling is
        # v1.1's compute saving over v1.0)
        for i, (sq, ex) in enumerate([(16, 64), (16, 64)]):
            x = _FireModule(sq, ex, dtype=self.dtype,
                            name=f"fire{i + 2}")(x, train=train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        for i, (sq, ex) in enumerate([(32, 128), (32, 128)]):
            x = _FireModule(sq, ex, dtype=self.dtype,
                            name=f"fire{i + 4}")(x, train=train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        for i, (sq, ex) in enumerate([(48, 192), (48, 192), (64, 256),
                                      (64, 256)]):
            x = _FireModule(sq, ex, dtype=self.dtype,
                            name=f"fire{i + 6}")(x, train=train)
        x = Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Conv(self.num_classes, (1, 1), dtype=jnp.float32,
                    name="head_conv")(x.astype(jnp.float32))
        return jnp.mean(nn.relu(x), axis=(1, 2))


class _DenseBlock(nn.Module):
    """DenseNet block: each layer concatenates its k new feature maps
    (bottleneck 1x1 -> 3x3) onto the running feature stack."""

    layers: int
    growth: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = _norm(train, self.dtype)
        for i in range(self.layers):
            h = nn.relu(norm(name=f"l{i}_bn1")(x))
            h = nn.Conv(4 * self.growth, (1, 1), use_bias=False,
                        dtype=self.dtype, name=f"l{i}_conv1")(h)
            h = nn.relu(norm(name=f"l{i}_bn2")(h))
            h = nn.Conv(self.growth, (3, 3), use_bias=False,
                        dtype=self.dtype, name=f"l{i}_conv2")(h)
            x = jnp.concatenate([x, h], axis=-1)
        return x


class DenseNet(nn.Module):
    """DenseNet-BC (ref model-zoo family: image_classifier.py
    "densenet-161"; default config = DenseNet-121, ``densenet161()``
    below builds the reference's 161 variant)."""

    num_classes: int = 1000
    stage_sizes: Tuple[int, ...] = (6, 12, 24, 16)  # DenseNet-121
    growth: int = 32
    stem_features: int = 64
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = _norm(train, self.dtype)
        x = nn.Conv(self.stem_features, (7, 7), (2, 2), use_bias=False,
                    dtype=self.dtype, name="stem_conv")(x)
        x = nn.relu(norm(name="stem_bn")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for s, layers in enumerate(self.stage_sizes):
            x = _DenseBlock(layers, self.growth, dtype=self.dtype,
                            name=f"dense{s + 1}")(x, train=train)
            if s < len(self.stage_sizes) - 1:  # transition: halve C, HW
                x = nn.relu(norm(name=f"trans{s + 1}_bn")(x))
                x = nn.Conv(x.shape[-1] // 2, (1, 1), use_bias=False,
                            dtype=self.dtype,
                            name=f"trans{s + 1}_conv")(x)
                x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = nn.relu(norm(name="final_bn")(x))
        x = jnp.mean(x, axis=(1, 2)).astype(jnp.float32)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="head")(x)


def densenet161(num_classes: int = 1000, dtype: Any = jnp.float32):
    """The reference's DenseNet-161 (growth 48, deeper stages)."""
    return DenseNet(num_classes=num_classes,
                    stage_sizes=(6, 12, 36, 24), growth=48,
                    stem_features=96, dtype=dtype)


class _InvertedResidual(nn.Module):
    """MobileNet v2 block: 1x1 expand -> depthwise 3x3 -> 1x1 project,
    residual when stride 1 and shapes match; relu6 activations."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    expand_ratio: int = 6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        norm = _norm(train, self.dtype)
        inp = x.shape[-1]
        h = x
        if self.expand_ratio != 1:
            h = nn.Conv(inp * self.expand_ratio, (1, 1), use_bias=False,
                        dtype=self.dtype, name="expand")(h)
            h = jnp.clip(norm(name="expand_bn")(h), 0, 6)
        c = h.shape[-1]
        h = nn.Conv(c, (3, 3), self.strides, use_bias=False,
                    feature_group_count=c, dtype=self.dtype,
                    name="dw")(h)
        h = jnp.clip(norm(name="dw_bn")(h), 0, 6)
        h = nn.Conv(self.filters, (1, 1), use_bias=False,
                    dtype=self.dtype, name="project")(h)
        h = norm(name="project_bn")(h)
        if self.strides == (1, 1) and inp == self.filters:
            return x + h
        return h


class MobileNetV2(nn.Module):
    """MobileNet v2 (ref model-zoo family: image_classifier.py
    "mobilenet-v2")."""

    num_classes: int = 1000
    width: float = 1.0
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        def w(f):
            return max(8, int(f * self.width))

        norm = _norm(train, self.dtype)
        x = nn.Conv(w(32), (3, 3), (2, 2), use_bias=False,
                    dtype=self.dtype, name="stem")(x)
        x = jnp.clip(norm(name="stem_bn")(x), 0, 6)
        # (expand_ratio, filters, repeats, first_stride)
        plan = [(1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2),
                (6, 64, 4, 2), (6, 96, 3, 1), (6, 160, 3, 2),
                (6, 320, 1, 1)]
        idx = 0
        for t, f, reps, s0 in plan:
            for r in range(reps):
                x = _InvertedResidual(
                    w(f), (s0, s0) if r == 0 else (1, 1),
                    expand_ratio=t, dtype=self.dtype,
                    name=f"block{idx}")(x, train=train)
                idx += 1
        x = nn.Conv(max(1280, w(1280)), (1, 1), use_bias=False,
                    dtype=self.dtype, name="head_conv")(x)
        x = jnp.clip(norm(name="head_bn")(x), 0, 6)
        x = jnp.mean(x, axis=(1, 2)).astype(jnp.float32)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="head")(x)


class _ConvBN(nn.Module):
    filters: int
    kernel: Tuple[int, int]
    strides: Tuple[int, int] = (1, 1)
    padding: str = "SAME"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Conv(self.filters, self.kernel, self.strides,
                    padding=self.padding, use_bias=False,
                    dtype=self.dtype, name="conv")(x)
        return nn.relu(_norm(train, self.dtype)(name="bn")(x))


class _MixedA(nn.Module):
    """Inception-v3 35x35 block: 1x1 | 5x5 | double-3x3 | pool-proj."""

    pool_features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        cb = partial(_ConvBN, dtype=self.dtype)
        b1 = cb(64, (1, 1), name="b1")(x, train)
        b5 = cb(48, (1, 1), name="b5_1")(x, train)
        b5 = cb(64, (5, 5), name="b5_2")(b5, train)
        b3 = cb(64, (1, 1), name="b3_1")(x, train)
        b3 = cb(96, (3, 3), name="b3_2")(b3, train)
        b3 = cb(96, (3, 3), name="b3_3")(b3, train)
        bp = nn.avg_pool(x, (3, 3), strides=(1, 1), padding="SAME")
        bp = cb(self.pool_features, (1, 1), name="bp")(bp, train)
        return jnp.concatenate([b1, b5, b3, bp], axis=-1)


class _MixedB(nn.Module):
    """Inception-v3 35->17 reduction."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        cb = partial(_ConvBN, dtype=self.dtype)
        b3 = cb(384, (3, 3), (2, 2), padding="VALID",
                name="b3")(x, train)
        bd = cb(64, (1, 1), name="bd_1")(x, train)
        bd = cb(96, (3, 3), name="bd_2")(bd, train)
        bd = cb(96, (3, 3), (2, 2), padding="VALID",
                name="bd_3")(bd, train)
        bp = nn.max_pool(x, (3, 3), strides=(2, 2), padding="VALID")
        return jnp.concatenate([b3, bd, bp], axis=-1)


class _MixedC(nn.Module):
    """Inception-v3 17x17 block with factorized 7x1/1x7 convs."""

    c7: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        cb = partial(_ConvBN, dtype=self.dtype)
        b1 = cb(192, (1, 1), name="b1")(x, train)
        b7 = cb(self.c7, (1, 1), name="b7_1")(x, train)
        b7 = cb(self.c7, (1, 7), name="b7_2")(b7, train)
        b7 = cb(192, (7, 1), name="b7_3")(b7, train)
        bd = cb(self.c7, (1, 1), name="bd_1")(x, train)
        bd = cb(self.c7, (7, 1), name="bd_2")(bd, train)
        bd = cb(self.c7, (1, 7), name="bd_3")(bd, train)
        bd = cb(self.c7, (7, 1), name="bd_4")(bd, train)
        bd = cb(192, (1, 7), name="bd_5")(bd, train)
        bp = nn.avg_pool(x, (3, 3), strides=(1, 1), padding="SAME")
        bp = cb(192, (1, 1), name="bp")(bp, train)
        return jnp.concatenate([b1, b7, bd, bp], axis=-1)


class _MixedD(nn.Module):
    """Inception-v3 17->8 reduction."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        cb = partial(_ConvBN, dtype=self.dtype)
        b3 = cb(192, (1, 1), name="b3_1")(x, train)
        b3 = cb(320, (3, 3), (2, 2), padding="VALID",
                name="b3_2")(b3, train)
        b7 = cb(192, (1, 1), name="b7_1")(x, train)
        b7 = cb(192, (1, 7), name="b7_2")(b7, train)
        b7 = cb(192, (7, 1), name="b7_3")(b7, train)
        b7 = cb(192, (3, 3), (2, 2), padding="VALID",
                name="b7_4")(b7, train)
        bp = nn.max_pool(x, (3, 3), strides=(2, 2), padding="VALID")
        return jnp.concatenate([b3, b7, bp], axis=-1)


class _MixedE(nn.Module):
    """Inception-v3 8x8 block with split 1x3/3x1 branches."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        cb = partial(_ConvBN, dtype=self.dtype)
        b1 = cb(320, (1, 1), name="b1")(x, train)
        b3 = cb(384, (1, 1), name="b3_1")(x, train)
        b3 = jnp.concatenate(
            [cb(384, (1, 3), name="b3_a")(b3, train),
             cb(384, (3, 1), name="b3_b")(b3, train)], axis=-1)
        bd = cb(448, (1, 1), name="bd_1")(x, train)
        bd = cb(384, (3, 3), name="bd_2")(bd, train)
        bd = jnp.concatenate(
            [cb(384, (1, 3), name="bd_a")(bd, train),
             cb(384, (3, 1), name="bd_b")(bd, train)], axis=-1)
        bp = nn.avg_pool(x, (3, 3), strides=(1, 1), padding="SAME")
        bp = cb(192, (1, 1), name="bp")(bp, train)
        return jnp.concatenate([b1, b3, bd, bp], axis=-1)


class InceptionV3(nn.Module):
    """Inception-v3 (ref model-zoo family: image_classifier.py
    "inception-v3"): factorized-conv mixed blocks; aux head omitted
    (BN training does not need it -- same stance as InceptionV1)."""

    num_classes: int = 1000
    dropout_rate: float = 0.5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        cb = partial(_ConvBN, dtype=self.dtype)
        x = cb(32, (3, 3), (2, 2), padding="VALID",
               name="stem1")(x, train)
        x = cb(32, (3, 3), padding="VALID", name="stem2")(x, train)
        x = cb(64, (3, 3), name="stem3")(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        x = cb(80, (1, 1), name="stem4")(x, train)
        x = cb(192, (3, 3), padding="VALID", name="stem5")(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        for i, pf in enumerate((32, 64, 64)):
            x = _MixedA(pf, dtype=self.dtype,
                        name=f"mixedA{i}")(x, train=train)
        x = _MixedB(dtype=self.dtype, name="mixedB")(x, train=train)
        for i, c7 in enumerate((128, 160, 160, 192)):
            x = _MixedC(c7, dtype=self.dtype,
                        name=f"mixedC{i}")(x, train=train)
        x = _MixedD(dtype=self.dtype, name="mixedD")(x, train=train)
        for i in range(2):
            x = _MixedE(dtype=self.dtype,
                        name=f"mixedE{i}")(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        x = Dropout(self.dropout_rate, deterministic=not train)(x)
        x = x.astype(jnp.float32)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="head")(x)
