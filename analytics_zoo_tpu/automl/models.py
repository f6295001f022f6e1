"""Tunable time-series models for AutoML / Zouwu.

TPU-native re-designs of the reference's searchable model set
(ref: pyzoo/zoo/automl/model/ -- VanillaLSTM.py, Seq2Seq.py,
MTNet_keras.py:614, tcn.py). Each is a plain flax module taking
``x [B, past_seq_len, F]`` and emitting ``[B, future_seq_len * T]``;
``TimeSequenceModel`` wraps one behind the fit_eval/evaluate/predict
contract the search engine drives (ref: model/abstract.py BaseModel),
training through the framework's own SPMD ``Estimator``.

MTNet (re-derived from the paper behind MTNet_keras.py): the history is
split into ``long_num`` memory blocks plus a short query window of
``time_step`` steps; a shared CNN+GRU encoder embeds each block; the
query attends over memory embeddings; [context; query] feeds the head,
with a parallel autoregressive linear term on the raw last steps --
the hot ops (conv, matmul attention, GRU) all map onto the MXU.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.automl import metrics as automl_metrics
from analytics_zoo_tpu.common.log import get_logger
from analytics_zoo_tpu.ops.dropout import Dropout

logger = get_logger(__name__)


class VanillaLSTM(nn.Module):
    """(ref: model/VanillaLSTM.py -- two stacked LSTMs + dense head)."""

    lstm_1_units: int = 32
    lstm_2_units: int = 32
    dropout_1: float = 0.2
    dropout_2: float = 0.2
    output_dim: int = 1

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = nn.RNN(nn.OptimizedLSTMCell(self.lstm_1_units),
                   name="lstm_1")(x)
        h = Dropout(self.dropout_1, deterministic=not train)(h)
        h = nn.RNN(nn.OptimizedLSTMCell(self.lstm_2_units),
                   name="lstm_2")(h)[:, -1]
        h = Dropout(self.dropout_2, deterministic=not train)(h)
        return nn.Dense(self.output_dim, name="head")(h)


class Seq2SeqForecaster(nn.Module):
    """(ref: model/Seq2Seq.py -- LSTM encoder/decoder): the encoder's
    final carry seeds a decoder unrolled ``future_seq_len`` steps; each
    step's input is the previous step's prediction (autoregressive
    decoding without teacher forcing, matching inference-time use)."""

    latent_dim: int = 128
    future_seq_len: int = 1
    target_dim: int = 1
    dropout: float = 0.2

    @nn.compact
    def __call__(self, x, train: bool = False):
        carry, _ = nn.RNN(nn.OptimizedLSTMCell(self.latent_dim),
                          return_carry=True, name="encoder")(x)
        cell = nn.OptimizedLSTMCell(self.latent_dim, name="decoder_cell")
        head = nn.Dense(self.target_dim, name="decoder_head")
        drop = Dropout(self.dropout, deterministic=not train)
        # first decoder input: the last observed target values
        step_in = x[:, -1, :self.target_dim]
        outs = []
        for _ in range(self.future_seq_len):  # static unroll: short
            carry, h = cell(carry, step_in)   # horizon, XLA-friendly
            step_in = head(drop(h))
            outs.append(step_in)
        return jnp.stack(outs, axis=1).reshape(
            x.shape[0], self.future_seq_len * self.target_dim)


class _MTNetEncoder(nn.Module):
    """Shared block encoder: causal-free CNN over the window, GRU over
    the conv features, attention-pooled to one embedding."""

    cnn_hidden: int = 32
    rnn_hidden: int = 32
    cnn_height: int = 2
    cnn_dropout: float = 0.2
    rnn_dropout: float = 0.2

    @nn.compact
    def __call__(self, w, train: bool = False):
        # w: [B, time_step, D] -> conv over time with full-width kernel
        h = nn.Conv(self.cnn_hidden, kernel_size=(self.cnn_height,),
                    padding="VALID", name="conv")(w)
        h = nn.relu(h)
        h = Dropout(self.cnn_dropout, deterministic=not train)(h)
        seq = nn.RNN(nn.GRUCell(self.rnn_hidden), name="gru")(h)
        seq = Dropout(self.rnn_dropout, deterministic=not train)(seq)
        # attention pooling over the conv-time axis
        score = nn.Dense(1, name="attn")(nn.tanh(seq))
        alpha = jax.nn.softmax(score, axis=1)
        return jnp.sum(alpha * seq, axis=1)  # [B, rnn_hidden]


class MTNet(nn.Module):
    """Memory time-series network (ref: model/MTNet_keras.py:614).

    Input [B, (long_num + 1) * time_step, D]: the leading
    ``long_num * time_step`` steps form the long-term memory blocks, the
    final ``time_step`` steps the short-term query window.
    """

    time_step: int = 4
    long_num: int = 4
    ar_size: int = 2
    cnn_hidden: int = 32
    rnn_hidden: int = 32
    cnn_height: int = 2
    cnn_dropout: float = 0.2
    rnn_dropout: float = 0.2
    output_dim: int = 1
    # leading input columns holding the raw target series (the AR
    # highway reads these; output_dim = future_seq_len * target_dim)
    target_dim: int = 1

    @nn.compact
    def __call__(self, x, train: bool = False):
        b, total, d = x.shape
        expect = (self.long_num + 1) * self.time_step
        if total != expect:
            raise ValueError(f"MTNet wants seq len {expect}, got {total}")
        mem = x[:, :self.long_num * self.time_step].reshape(
            b * self.long_num, self.time_step, d)
        query = x[:, self.long_num * self.time_step:]

        encoder = _MTNetEncoder(self.cnn_hidden, self.rnn_hidden,
                                self.cnn_height, self.cnn_dropout,
                                self.rnn_dropout, name="encoder")
        m = encoder(mem, train).reshape(b, self.long_num, -1)
        u = encoder(query, train)  # [B, H] -- shared weights

        # attention of query over memory embeddings
        logits = jnp.einsum("blh,bh->bl", m, u) / jnp.sqrt(
            jnp.asarray(m.shape[-1], x.dtype))
        p = jax.nn.softmax(logits, axis=-1)
        context = jnp.einsum("bl,blh->bh", p, m)

        if self.target_dim > d:
            raise ValueError(f"MTNet target_dim={self.target_dim} "
                             f"exceeds input width {d}")
        nonlinear = nn.Dense(self.output_dim, name="head")(
            jnp.concatenate([context, u], axis=-1))
        # autoregressive highway on the raw last ar_size target values
        ar_in = x[:, -self.ar_size:, :self.target_dim].reshape(b, -1)
        linear = nn.Dense(self.output_dim, name="ar")(ar_in)
        return nonlinear + linear


class TCN(nn.Module):
    """Temporal convolutional network (ref: model/tcn.py -- stacked
    residual blocks of dilated causal convolutions)."""

    levels: int = 3
    hidden: int = 30
    kernel_size: int = 3
    dropout: float = 0.1
    output_dim: int = 1

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = x
        for i in range(self.levels):
            dilation = 2 ** i
            pad = (self.kernel_size - 1) * dilation
            res = h
            for j in range(2):
                # left-pad for causality: output[t] sees input[<=t]
                hp = jnp.pad(h, ((0, 0), (pad, 0), (0, 0)))
                h = nn.Conv(self.hidden, (self.kernel_size,),
                            kernel_dilation=dilation, padding="VALID",
                            name=f"conv_{i}_{j}")(hp)
                h = nn.relu(h)
                h = Dropout(self.dropout, deterministic=not train)(h)
            if res.shape[-1] != self.hidden:
                res = nn.Dense(self.hidden, name=f"res_{i}")(res)
            h = nn.relu(h + res)
        return nn.Dense(self.output_dim, name="head")(h[:, -1])


# ---------------------------------------------------------------------- #
#                          TimeSequenceModel                             #
# ---------------------------------------------------------------------- #

def build_forecast_module(config: Dict[str, Any], future_seq_len: int,
                          n_targets: int) -> nn.Module:
    """Search-space config -> flax module (the 'model' key selects the
    family, mirroring the reference recipes' model field)."""
    out = future_seq_len * n_targets
    kind = str(config.get("model", "LSTM")).upper()
    if kind in ("LSTM", "VANILLALSTM"):
        return VanillaLSTM(
            lstm_1_units=int(config.get("lstm_1_units", 32)),
            lstm_2_units=int(config.get("lstm_2_units", 32)),
            dropout_1=float(config.get("dropout_1", 0.2)),
            dropout_2=float(config.get("dropout_2", 0.2)),
            output_dim=out)
    if kind == "SEQ2SEQ":
        return Seq2SeqForecaster(
            latent_dim=int(config.get("latent_dim", 64)),
            future_seq_len=future_seq_len, target_dim=n_targets,
            dropout=float(config.get("dropout", 0.2)))
    if kind == "MTNET":
        return MTNet(
            time_step=int(config.get("time_step", 4)),
            long_num=int(config.get("long_num", 4)),
            ar_size=int(config.get("ar_size", 2)),
            cnn_hidden=int(config.get("cnn_hidden", 32)),
            rnn_hidden=int(config.get("rnn_hidden", 32)),
            cnn_height=int(config.get("cnn_height", 2)),
            cnn_dropout=float(config.get("cnn_dropout", 0.2)),
            rnn_dropout=float(config.get("rnn_dropout", 0.2)),
            output_dim=out, target_dim=n_targets)
    if kind == "TCN":
        return TCN(levels=int(config.get("levels", 3)),
                   hidden=int(config.get("hidden", 30)),
                   kernel_size=int(config.get("kernel_size", 3)),
                   dropout=float(config.get("dropout", 0.1)),
                   output_dim=out)
    raise ValueError(f"unknown model kind {kind!r}")


class TimeSequenceModel:
    """fit_eval/evaluate/predict wrapper around one forecast module
    (ref: model/time_sequence.py TimeSequenceModel, model/abstract.py)."""

    def __init__(self, future_seq_len: int = 1, n_targets: int = 1):
        self.future_seq_len = future_seq_len
        self.n_targets = n_targets
        self.config: Dict[str, Any] = {}
        self.estimator = None
        self._xgb = None  # gradient-boosted-trees delegate (model: XGBoost)

    @staticmethod
    def _is_xgb(config: Dict[str, Any]) -> bool:
        return str(config.get("model", "")).upper() == "XGBOOST"

    # keys that tune the training loop, not the architecture: changing
    # them must NOT discard the trained estimator (fit_eval is called
    # repeatedly to continue training)
    _LOOP_KEYS = ("epochs", "batch_size", "metric")

    def _arch_of(self, config: Dict[str, Any]) -> Dict[str, Any]:
        return {k: v for k, v in config.items()
                if k not in self._LOOP_KEYS}

    def _ensure_estimator(self, config: Dict[str, Any]):
        from analytics_zoo_tpu.learn.estimator import Estimator
        from analytics_zoo_tpu.learn.optim import Adam

        if (self.estimator is None or
                self._arch_of(config) != self._arch_of(self.config)):
            self.config = dict(config)
            module = build_forecast_module(config, self.future_seq_len,
                                           self.n_targets)
            self.estimator = Estimator(
                module, loss="mse",
                optimizer=Adam(float(config.get("lr", 1e-3))))
        else:
            self.config = dict(config)  # refresh loop keys only
        return self.estimator

    def fit_eval(self, x: np.ndarray, y: np.ndarray,
                 validation_data: Optional[Tuple] = None,
                 unscale_fn=None, verbose: int = 0, **config) -> float:
        """Train ``config['epochs']`` epochs, return the reward metric on
        the validation set (train set when absent). Called repeatedly by
        the scheduler: the estimator persists, so successive calls
        continue training (ref: abstract.py fit_eval contract).

        ``unscale_fn`` maps [B, future*T] scaled targets back to data
        units before scoring -- ratio metrics (mape/smape) are
        meaningless on standardized values, and search rewards must be
        comparable with pipeline.evaluate's unscaled numbers.
        """
        if self._is_xgb(config):
            return self._fit_eval_xgb(x, y, validation_data, unscale_fn,
                                      config)
        self._xgb = None  # config switched family: drop a stale delegate
        est = self._ensure_estimator(config)
        y2 = y.reshape(len(y), -1)
        batch_size = int(config.get("batch_size", 32))
        batch_size = max(1, min(batch_size, len(x)))
        est.fit((x, y2), batch_size=batch_size,
                epochs=est.epoch + int(config.get("epochs", 1)))
        return self._score(x, y2, validation_data, unscale_fn, config)

    def _score(self, x, y2, validation_data, unscale_fn, config) -> float:
        """Reward on validation (train when absent), in DATA units when
        an unscale_fn is given -- shared by the neural and XGBoost
        fit_eval paths so search rewards stay comparable."""
        vx, vy = (x, y2) if validation_data is None else (
            validation_data[0],
            np.asarray(validation_data[1]).reshape(
                len(validation_data[1]), -1))
        metric = str(config.get("metric", "mse"))
        pred = self.predict(vx)
        if unscale_fn is not None:
            vy, pred = unscale_fn(vy), unscale_fn(pred)
        return automl_metrics.evaluate(metric, vy, pred)

    def _fit_eval_xgb(self, x, y, validation_data, unscale_fn,
                      config) -> float:
        """XGBoost in the same TimeSequenceModel slot (ref: the
        reference searches XGBoost through the identical fit_eval
        contract, automl/model/XGBoost.py); trees retrain from scratch
        each call (boosting has no warm continuation here)."""
        from analytics_zoo_tpu.automl.xgboost import XGBoost as XGBModel

        self.config = dict(config)
        self._xgb = XGBModel("regressor", config=config)
        y2 = np.asarray(y).reshape(len(y), -1)
        self._xgb.fit(np.asarray(x).reshape(len(x), -1), y2)
        return self._score(x, y2, validation_data, unscale_fn, config)

    def predict(self, x: np.ndarray, batch_size: int = 128) -> np.ndarray:
        if self._xgb is not None:
            return self._xgb.predict(
                np.asarray(x).reshape(len(x), -1))
        if self.estimator is None:
            raise RuntimeError("model not fitted")
        return np.asarray(self.estimator.predict(x, batch_size=batch_size))

    def predict_with_uncertainty(self, x: np.ndarray, n_iter: int = 10):
        """Monte-Carlo dropout: n_iter stochastic forwards -> (mean, std)
        (ref: model mc=True predict_with_uncertainty)."""
        est = self.estimator
        if est is None:
            raise RuntimeError("model not fitted")
        adapter = est.adapter

        @jax.jit
        def mc_forward(variables, xb, rng):
            preds, _ = adapter.apply(variables, xb, training=True, rng=rng)
            return preds

        rng = jax.random.PRNGKey(0)
        outs = []
        for i in range(n_iter):
            rng, sub = jax.random.split(rng)
            outs.append(np.asarray(
                mc_forward(est.variables, jnp.asarray(x), sub)))
        stack = np.stack(outs)
        return stack.mean(axis=0), stack.std(axis=0)

    def evaluate(self, x, y, metrics=("mse",)) -> Dict[str, float]:
        pred = self.predict(x)
        y2 = np.asarray(y).reshape(len(y), -1)
        return automl_metrics.evaluate_all(metrics, y2, pred)

    # ----------------------------------------------------- persistence --
    def save(self, dir_path: str) -> None:
        from analytics_zoo_tpu.automl.feature import _jsonable

        os.makedirs(dir_path, exist_ok=True)
        meta = {"future_seq_len": self.future_seq_len,
                "n_targets": self.n_targets,
                "config": _jsonable(self.config)}
        with open(os.path.join(dir_path, "ts_model.json"), "w") as f:
            json.dump(meta, f)
        if self._xgb is not None:
            self._xgb.save(os.path.join(dir_path, "xgb"))
        elif self.estimator is not None:
            self.estimator.save(os.path.join(dir_path, "ckpt"))

    @classmethod
    def restore(cls, dir_path: str) -> "TimeSequenceModel":
        with open(os.path.join(dir_path, "ts_model.json")) as f:
            meta = json.load(f)
        model = cls(future_seq_len=meta["future_seq_len"],
                    n_targets=meta["n_targets"])
        if cls._is_xgb(meta["config"]):
            from analytics_zoo_tpu.automl.xgboost import (
                XGBoost as XGBModel)

            model.config = dict(meta["config"])
            model._xgb = XGBModel.restore(os.path.join(dir_path, "xgb"))
            return model
        model._ensure_estimator(meta["config"])
        ckpt = os.path.join(dir_path, "ckpt")
        if os.path.isdir(ckpt):
            model.estimator.load(ckpt)
        return model

    # ------------------------------------------------- state (in-memory) --
    def state_bytes(self) -> bytes:
        """Serialized weights for cross-process trial results."""
        import io

        from flax.serialization import to_bytes

        if self._xgb is not None:
            import pickle

            return pickle.dumps(self._xgb)
        buf = io.BytesIO()
        est = self.estimator
        variables = jax.device_get(est.variables)
        buf.write(to_bytes(variables))
        return buf.getvalue()

    def load_state_bytes(self, blob: bytes, config: Dict[str, Any],
                         example_x: np.ndarray) -> None:
        from flax.serialization import from_bytes

        if self._is_xgb(config):
            import pickle

            self.config = dict(config)
            self._xgb = pickle.loads(blob)
            return
        self._xgb = None
        est = self._ensure_estimator(config)
        est._ensure_built(example_x)
        est.variables = from_bytes(jax.device_get(est.variables), blob)
        est._place_state()
