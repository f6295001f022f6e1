"""The one generator of training traffic: a cell's traffic file names a
``kind`` and its parameters under ``"data"``, and this module makes the
host arrays from the seed. The same seed gives the same arrays; a new
mix of an existing kind is a new data file and no code.

Kinds:

``token_spans`` -- SQuAD-shaped fine-tuning batches (copied from
  ``chip_smoke.squad_batches``): ``n`` rows of ``seq_len`` token ids
  drawn uniformly from the vocabulary, and answer spans that start in a
  narrow window (``seq_len/8 .. seq_len/4``), so that a position prior
  is learnable within a few dozen steps and a falling loss is a real
  check.
``images_uint8`` -- ImageNet-shaped batches as a host pipeline hands
  them over after decode and crop: uint8 ``[n, size, size, 3]``. Labels
  follow a Zipf prior over the classes and every class tints its images
  (a per-class channel mean over noise), so both the classifier's bias
  and its features have something to learn.
"""

from __future__ import annotations

import numpy as np


def _rows(data: dict) -> int:
    return int(data["batch"]) * int(data["steps_per_epoch"])


def _token_spans(data: dict, vocab: int, rng) -> tuple:
    n, seq = _rows(data), int(data["seq_len"])
    x = {"input_ids": rng.integers(0, vocab, (n, seq), dtype=np.int32)}
    start = rng.integers(seq // 8, seq // 4, n)
    end = start + rng.integers(0, seq // 16 + 1, n)
    return x, np.stack([start, end], axis=1).astype(np.int32)


def _images_uint8(data: dict, classes: int, rng) -> tuple:
    """``distinct_images`` different images, each with a class, repeated
    over the epoch's rows in a seeded order: making every row's pixels
    anew would cost seconds of set-up and change nothing on the device."""
    n, size = _rows(data), int(data["image_size"])
    pool = int(data["distinct_images"])
    prior = 1.0 / np.arange(1, classes + 1) ** float(data["label_zipf"])
    labels = rng.choice(classes, size=pool, p=prior / prior.sum())
    tint = rng.integers(0, 128, (classes, 3), dtype=np.uint8)
    noise = np.frombuffer(rng.bytes(pool * size * size * 3), np.uint8) >> 1
    images = noise.reshape(pool, size, size, 3) + tint[labels][:, None, None]
    rows = rng.integers(0, pool, n)
    return images[rows], labels[rows].astype(np.int32)


def generate(data: dict, config: dict, seed: int) -> tuple:
    """``(features, labels)`` for one epoch of the cell's traffic."""
    rng = np.random.default_rng(seed)
    kind = data["kind"]
    if kind == "token_spans":
        return _token_spans(data, int(config["vocab_size"]), rng)
    if kind == "images_uint8":
        return _images_uint8(data, int(config["num_classes"]), rng)
    raise ValueError(f"unknown traffic kind {kind!r}")
