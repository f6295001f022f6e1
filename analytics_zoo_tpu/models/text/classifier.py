"""Text classification model.

The analog of ``TextClassifier`` (ref: zoo/.../models/textclassification/
TextClassifier.scala, pyzoo/zoo/models/textclassification): token-id
sequences -> embedding (optionally pretrained/frozen) -> CNN / LSTM / GRU
encoder -> dense -> class logits.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.models.common import ZooModel, register_model
from analytics_zoo_tpu.ops.dropout import Dropout


class TextClassifierNet(nn.Module):
    class_num: int
    vocab: int
    embed_dim: int
    encoder: str = "cnn"
    encoder_output_dim: int = 256
    sequence_length: int = 500

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = nn.Embed(self.vocab + 1, self.embed_dim,
                     name="embedding")(x.astype(jnp.int32))
        if self.encoder == "cnn":
            h = nn.relu(nn.Conv(self.encoder_output_dim, (5,),
                                name="conv")(h))
            h = jnp.max(h, axis=1)  # global max pool over time
        elif self.encoder == "lstm":
            h = nn.RNN(nn.OptimizedLSTMCell(self.encoder_output_dim),
                       name="lstm")(h)[:, -1]
        elif self.encoder == "gru":
            h = nn.RNN(nn.GRUCell(self.encoder_output_dim),
                       name="gru")(h)[:, -1]
        else:
            raise ValueError(f"unknown encoder {self.encoder!r}")
        h = Dropout(0.2, deterministic=not train)(h)
        h = nn.relu(nn.Dense(128, name="fc")(h))
        return nn.Dense(self.class_num, name="head")(h)


@register_model
class TextClassifier(ZooModel):
    """(ref: TextClassifier.scala). Labels are 0-based class ids."""

    default_loss = "sparse_categorical_crossentropy"
    default_optimizer = "adam"
    default_metrics = ("accuracy",)

    def __init__(self, class_num: int, vocab: int = 20000,
                 embed_dim: int = 200, sequence_length: int = 500,
                 encoder: str = "cnn", encoder_output_dim: int = 256):
        super().__init__(class_num=class_num, vocab=vocab,
                         embed_dim=embed_dim,
                         sequence_length=sequence_length, encoder=encoder,
                         encoder_output_dim=encoder_output_dim)

    def _build_module(self):
        c = self._config
        return TextClassifierNet(
            class_num=c["class_num"], vocab=c["vocab"],
            embed_dim=c["embed_dim"], encoder=c["encoder"],
            encoder_output_dim=c["encoder_output_dim"],
            sequence_length=c["sequence_length"])

    def _example_input(self):
        return np.ones((1, self._config["sequence_length"]), np.int32)
