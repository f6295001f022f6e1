"""BERT fine-tune estimators: sequence classification and NER.

The analog of the TFPark BERT estimator family
(ref: pyzoo/zoo/tfpark/text/estimator/bert_classifier.py -- pooled
[CLS] -> dense classes; bert_ner.py -- per-token dense tags; both built
on the model_fn pattern of bert_base.py:115-134; the SQuAD sibling
lives in bert_squad.py). Same flash-attention encoder and bf16 story
as BERTSQuAD.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.keras.layers.transformer import BERTModule
from analytics_zoo_tpu.models.common import ZooModel, register_model
from analytics_zoo_tpu.ops.dropout import Dropout


class _BERTHeadModule(nn.Module):
    """BERT encoder + a classification head: pooled [CLS] (sequence
    tasks) or every token (NER)."""

    vocab: int
    num_classes: int
    per_token: bool
    hidden_size: int = 768
    n_block: int = 12
    n_head: int = 12
    intermediate_size: int = 3072
    max_position_len: int = 512
    hidden_dropout: float = 0.1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        seq, pooled = BERTModule(
            vocab=self.vocab, hidden_size=self.hidden_size,
            n_block=self.n_block, n_head=self.n_head,
            intermediate_size=self.intermediate_size,
            max_position_len=self.max_position_len,
            hidden_dropout=self.hidden_dropout, attn_dropout=0.0,
            dtype=self.dtype, name="bert")(x, train=train)
        h = seq if self.per_token else pooled
        h = Dropout(self.hidden_dropout, deterministic=not train)(h)
        return nn.Dense(self.num_classes, name="head")(
            h.astype(jnp.float32))


class _BERTEstimatorBase(ZooModel):
    default_loss = "sparse_categorical_crossentropy"
    default_optimizer = "adam"
    default_metrics = ("accuracy",)
    per_token = False

    def __init__(self, num_classes: int, vocab: int,
                 hidden_size: int = 768, n_block: int = 12,
                 n_head: int = 12, intermediate_size: int = 3072,
                 max_position_len: int = 512,
                 hidden_dropout: float = 0.1, dtype: str = "float32"):
        super().__init__(num_classes=num_classes, vocab=vocab,
                         hidden_size=hidden_size, n_block=n_block,
                         n_head=n_head,
                         intermediate_size=intermediate_size,
                         max_position_len=max_position_len,
                         hidden_dropout=hidden_dropout, dtype=dtype)

    def _build_module(self):
        c = self._config
        return _BERTHeadModule(
            vocab=c["vocab"], num_classes=c["num_classes"],
            per_token=self.per_token, hidden_size=c["hidden_size"],
            n_block=c["n_block"], n_head=c["n_head"],
            intermediate_size=c["intermediate_size"],
            max_position_len=c["max_position_len"],
            hidden_dropout=c["hidden_dropout"],
            dtype=jnp.dtype(c["dtype"]))

    def _example_input(self):
        return {"input_ids": np.zeros((1, 16), np.int32)}


@register_model
class BERTClassifier(_BERTEstimatorBase):
    """Sequence classification over the pooled [CLS]
    (ref: bert_classifier.py BERTClassifier). fit expects
    x = {"input_ids", optional "token_type_ids"/"attention_mask"},
    y = [B] int class ids."""

    per_token = False


IGNORE_INDEX = -1


def token_cross_entropy(preds, labels):
    """Per-token mean CE: preds [B, L, C] logits, labels [B, L] ids.
    Positions labelled ``IGNORE_INDEX`` (-1) -- padding -- contribute
    nothing to the loss."""
    import jax

    c = preds.shape[-1]
    logp = jax.nn.log_softmax(
        preds.astype(jnp.float32).reshape(-1, c), -1)
    ids = jnp.asarray(labels).reshape(-1).astype(jnp.int32)
    keep = (ids != IGNORE_INDEX).astype(jnp.float32)
    safe = jnp.maximum(ids, 0)
    nll = -jnp.take_along_axis(logp, safe[:, None], -1)[:, 0]
    return jnp.sum(nll * keep) / jnp.maximum(jnp.sum(keep), 1.0)


@register_model
class BERTNER(_BERTEstimatorBase):
    """Token-level tagging (ref: bert_ner.py BERTNER). fit expects
    y = [B, L] int tag ids, with padding positions labelled
    ``IGNORE_INDEX`` (-1); predictions are [B, L, num_classes]
    logits."""

    per_token = True
    default_loss = staticmethod(token_cross_entropy)
    default_metrics = ()  # per-token; see token_accuracy

    @staticmethod
    def decode_tags(logits) -> np.ndarray:
        """[B, L, C] logits -> [B, L] argmax tag ids."""
        return np.argmax(np.asarray(logits), axis=-1)

    @staticmethod
    def token_accuracy(logits, labels) -> float:
        """Accuracy over real tokens only (labels == IGNORE_INDEX are
        padding and excluded)."""
        tags = BERTNER.decode_tags(logits)
        labels = np.asarray(labels)
        keep = labels != IGNORE_INDEX
        total = max(int(keep.sum()), 1)
        return float(((tags == labels) & keep).sum() / total)
