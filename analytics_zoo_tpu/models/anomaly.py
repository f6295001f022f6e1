"""Anomaly detection via LSTM forecasting.

The analog of ``AnomalyDetector`` (ref: zoo/.../models/anomalydetection/
AnomalyDetector.scala, pyzoo/zoo/models/anomalydetection): stacked LSTMs
predict the next value of a feature sequence; the top-N largest
|y - y_hat| distances are flagged anomalous (unsupervised).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import flax.linen as nn
import numpy as np

from analytics_zoo_tpu.models.common import ZooModel, register_model
from analytics_zoo_tpu.ops.dropout import Dropout


class AnomalyDetectorNet(nn.Module):
    hidden_layers: Tuple[int, ...]
    dropouts: Tuple[float, ...]

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = x
        for i, (units, rate) in enumerate(
                zip(self.hidden_layers, self.dropouts)):
            h = nn.RNN(nn.OptimizedLSTMCell(units), name=f"lstm_{i}")(h)
            h = Dropout(rate, deterministic=not train)(h)
        return nn.Dense(1, name="head")(h[:, -1])


@register_model
class AnomalyDetector(ZooModel):
    """(ref: AnomalyDetector.scala). Input [B, unroll, features];
    regression target is the next value."""

    default_loss = "mse"
    default_optimizer = "rmsprop"
    default_metrics = ("mse",)

    def __init__(self, feature_shape: Sequence[int],
                 hidden_layers: Sequence[int] = (8, 32, 15),
                 dropouts: Sequence[float] = (0.2, 0.2, 0.2)):
        if len(hidden_layers) != len(dropouts):
            raise ValueError("hidden_layers and dropouts must align")
        super().__init__(feature_shape=list(feature_shape),
                         hidden_layers=list(hidden_layers),
                         dropouts=list(dropouts))

    def _build_module(self):
        c = self._config
        return AnomalyDetectorNet(hidden_layers=tuple(c["hidden_layers"]),
                                  dropouts=tuple(c["dropouts"]))

    def _example_input(self):
        return np.zeros((1,) + tuple(self._config["feature_shape"]),
                        np.float32)

    @staticmethod
    def unroll(data, unroll_length: int):
        """Sliding windows: [N, F] -> (x [M, unroll, F], y [M])
        (ref: AnomalyDetector.scala unroll)."""
        data = np.asarray(data, np.float32)
        if data.ndim == 1:
            data = data[:, None]
        n = len(data) - unroll_length
        if n <= 0:
            raise ValueError("series shorter than unroll_length")
        x = np.stack([data[i:i + unroll_length] for i in range(n)])
        y = data[unroll_length:, 0]
        return x, y

    @staticmethod
    def detect_anomalies(y_true, y_pred, anomaly_size: int):
        """Indices + threshold of the top-``anomaly_size`` forecast errors
        (ref: AnomalyDetector.scala detectAnomalies)."""
        y_true = np.asarray(y_true).reshape(-1)
        y_pred = np.asarray(y_pred).reshape(-1)
        dist = np.abs(y_true - y_pred)
        idx = np.argsort(-dist)[:anomaly_size]
        threshold = float(dist[idx[-1]]) if len(idx) else float("inf")
        return np.sort(idx), threshold
