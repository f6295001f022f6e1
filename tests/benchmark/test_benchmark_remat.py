"""``train_remat_device_ms`` on the made-up reduction of
``test_benchmark_trinity.py`` (its ``ctx`` fixture): the rows JAX names
``rematted_computation`` count, the first forward's and the backward's
own do not, and a program without ``nn.remat`` reads nothing."""

import pytest
from test_benchmark_trinity import config, ctx, data  # noqa: F401 (fixtures)

from benchmark.layer_metrics import train_remat_device_ms
from benchmark.lib import scope_reduce


def test_remat_reader_counts_the_second_forward_only(ctx):
    # checkpoint/rematted_computation/layer_*/moe/moe_combine alone;
    # checkpoint/layer_*/moe/moe_experts is the backward proper
    assert train_remat_device_ms.read(ctx) == 4.0


@pytest.mark.parametrize("reduced", [
    {"attention_ms": {}, "modules": [
        {"scope": "squad/bert/encoder_*/ffn_in", "total_ms": 9.0},
        {"scope": "optimizer", "total_ms": 1.0}]},
    None,                     # no device plane: a CPU rehearsal
])
def test_remat_reader_finds_nothing_without_remat(ctx, monkeypatch,
                                                  reduced):
    monkeypatch.setattr(scope_reduce, "for_cell", lambda ctx: reduced)
    assert train_remat_device_ms.read(ctx) is None
