"""The traffic generator: the same seed gives the same arrays, the
shapes and ranges are what the cells' files say."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPANS = {"kind": "token_spans", "batch": 4, "seq_len": 64,
         "steps_per_epoch": 5}
IMAGES = {"kind": "images_uint8", "batch": 4, "image_size": 16,
          "steps_per_epoch": 6, "distinct_images": 8, "label_zipf": 1.0}


def _same(a, b):
    a, b = (t["input_ids"] if isinstance(t, dict) else t for t in (a, b))
    return np.array_equal(a, b)


@pytest.mark.parametrize("data,config", [
    (SPANS, {"vocab_size": 100}), (IMAGES, {"num_classes": 10})])
def test_same_seed_same_arrays(data, config):
    x1, y1 = traffic.generate(data, config, 7)
    x2, y2 = traffic.generate(data, config, 7)
    x3, y3 = traffic.generate(data, config, 8)
    assert _same(x1, x2) and np.array_equal(y1, y2)
    assert not _same(x1, x3)


def test_token_spans_shape_and_window():
    x, y = traffic.generate(SPANS, {"vocab_size": 100}, 0)
    ids = x["input_ids"]
    assert ids.shape == (20, 64) and ids.dtype == np.int32
    assert ids.min() >= 0 and ids.max() < 100
    assert y.shape == (20, 2) and y.dtype == np.int32
    # answers start in [L/8, L/4) and run at most L/16 tokens
    assert (y[:, 0] >= 8).all() and (y[:, 0] < 16).all()
    assert ((y[:, 1] - y[:, 0]) >= 0).all()
    assert ((y[:, 1] - y[:, 0]) <= 4).all()


def test_images_shape_labels_and_pool():
    x, y = traffic.generate(IMAGES, {"num_classes": 10}, 0)
    assert x.shape == (24, 16, 16, 3) and x.dtype == np.uint8
    assert y.shape == (24,) and y.dtype == np.int32
    assert y.min() >= 0 and y.max() < 10
    distinct = {row.tobytes() for row in x}
    assert 1 < len(distinct) <= 8
    # an image keeps its label wherever it is repeated
    by_image = {}
    for row, label in zip(x, y):
        assert by_image.setdefault(row.tobytes(), int(label)) == int(label)


def test_unknown_kind_is_an_error():
    with pytest.raises(ValueError):
        traffic.generate({"kind": "nope", "batch": 1, "steps_per_epoch": 1},
                         {}, 0)


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(os.path.join(REPO, "benchmark", "workloads"))
    if f.endswith(".json")))
def test_each_cell_file_has_a_rehearsal_size_that_generates(name):
    with open(os.path.join(REPO, "benchmark", "workloads", name)) as f:
        cell = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           cell["config"] + ".json")) as f:
        config = json.load(f)
    data = {**cell["data"], **cell["rehearsal"]}
    config = {**config, **config["rehearsal"]}
    x, y = traffic.generate(data, config, 0)
    assert len(y) == data["batch"] * data["steps_per_epoch"]
    assert data["warmup_steps"] <= data["steps_per_epoch"]
    assert data["batch"] % cell["chips"] == 0
