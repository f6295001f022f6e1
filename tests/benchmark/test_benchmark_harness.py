"""BENCHMARK.json against its contract, and the harness as data: every
cell resolves to files, a new cell/config/metric is found as NEW files,
the command refuses to measure on the CPU, and the rehearsal switch
prints the contract's line with no timing in it."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark", "tests/benchmark"]
    assert bench["command"][1].startswith("benchmark/")
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])


def test_names_units_and_entries(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer")
                          and "metric" or group, e["name"]))
    assert len(names) == len(set(names)), "a name appears twice"
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in end_to_end
        assert 1 <= len(m["layer"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        cells = {w["name"] for w in bench["workloads"]}
        assert set(m.get("workloads", [])) <= cells
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_resolves_to_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        cell = _load("benchmark", "workloads", w["name"] + ".json")
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["name"] == w["name"]
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "runners", cell["runner"] + ".py"))
        entry = configs[w["config"]]
        assert entry["file"] == f"benchmark/configs/{w['config']}.json"
        config = _load(entry["file"])
        assert config["reduced"] == entry["reduced"]
        for dotted in (config["model"]["factory"], config["flops"],
                       config["reference"]["forward"],
                       config["optimizer"]["factory"]):
            module = dotted.rpartition(".")[0].replace(".", os.sep)
            assert os.path.isfile(os.path.join(REPO, module + ".py")), dotted
        for key in config["model"]["kwargs_from"].values():
            assert key in config
        assert config["reference"]["tolerance_why"]
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py")), m["name"]


def test_files_under_paths_use_only_name_characters(bench):
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    listed = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard",
         "--"] + bench["paths"], cwd=REPO, capture_output=True, text=True)
    files = listed.stdout.split()
    if listed.returncode != 0 or not files:     # not a git checkout
        files = [os.path.relpath(os.path.join(d, f), REPO)
                 for p in bench["paths"]
                 for d, _, fs in os.walk(os.path.join(REPO, p))
                 if "__pycache__" not in d and ".cache" not in d
                 for f in fs]
    assert files
    for path in files:
        assert allowed.match(path), path


def _run(root, *args, rehearsal, devices=1, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=REPO)
    env.pop("ZOO_BENCH_REHEARSAL", None)
    if rehearsal:
        env["ZOO_BENCH_REHEARSAL"] = "1"
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)


def test_refuses_to_measure_on_the_cpu(bench):
    cell = bench["workloads"][0]["name"]
    p = _run(REPO, "--workload", cell, "--seed", "1", "--seconds", "1",
             "--trace", "0", rehearsal=False)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def _tree_digest(root):
    digest = {}
    for d, _, fs in os.walk(root):
        if "__pycache__" in d or ".cache" in d:
            continue
        for f in fs:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                digest[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return digest


def test_new_cell_config_and_metric_are_new_files_only(bench, tmp_path):
    """A later PR's view: copy the benchmark, add a configuration, a
    four-chip cell and a per-layer metric as files of their own plus
    entries, touch no file that was there, and run the new cell -- here
    under the rehearsal switch, on four virtual CPU devices, traced."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    before = _tree_digest(os.path.join(root, "benchmark"))

    config = _load("benchmark", "configs", "bert-base-squad.json")
    config["name"] = "dummy-bert"
    cell = _load("benchmark", "workloads",
                 "bert-base-squad.fit-dp4-b128-l384.json")
    cell.update(name="dummy-bert.fit-tiny", config="dummy-bert")
    with open(os.path.join(root, "benchmark", "configs",
                           "dummy-bert.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "benchmark", "workloads",
                           "dummy-bert.fit-tiny.json"), "w") as f:
        json.dump(cell, f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "dummy_epochs.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['window']['epochs']\n")
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "dummy_nothing_to_read.py"), "w") as f:
        f.write("def read(ctx):\n    return None\n")
    extended = json.loads(json.dumps(bench))
    extended["configs"].append({
        "name": "dummy-bert", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/dummy-bert.json"})
    extended["workloads"].append({
        "name": "dummy-bert.fit-tiny", "config": "dummy-bert",
        "traffic": "fit-tiny", "chips": 4, "why": "test"})
    for name in ("dummy_epochs", "dummy_nothing_to_read"):
        extended["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "entry points",
            "moves": "train_samples_per_s",
            "workloads": ["dummy-bert.fit-tiny"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(extended, f)

    p = _run(root, "--workload", "dummy-bert.fit-tiny", "--seed", "5",
             "--seconds", "0.5", "--trace", "1", rehearsal=True, devices=4)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    # exactly the contract's keys; a CPU trace holds no device plane, so
    # no busy_s/window_s/breakdown, and no timing of any kind
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    metrics = line["metrics"]
    assert all(m["unit"] == "count" for m in metrics.values())
    assert metrics["compile_requests_in_window"]["value"] == 0
    assert metrics["dummy_epochs"]["value"] >= 1      # the new reader ran
    assert "dummy_nothing_to_read" not in metrics     # None is left out
    # metrics of other cells are not this cell's
    after = _tree_digest(os.path.join(root, "benchmark"))
    assert {k: after[k] for k in before} == before    # nothing edited
    assert set(after) - set(before) == {
        os.path.join("configs", "dummy-bert.json"),
        os.path.join("workloads", "dummy-bert.fit-tiny.json"),
        os.path.join("layer_metrics", "dummy_epochs.py"),
        os.path.join("layer_metrics", "dummy_nothing_to_read.py")}


def test_unknown_cell_fails_without_a_result(bench):
    p = _run(REPO, "--workload", "no-such.cell", "--seed", "1", "--seconds",
             "1", "--trace", "0", rehearsal=True)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("epoch_seconds, want", [
    ([2.0, 2.0, 2.0, 2.0], 50.0),                 # steady
    ([2.0, 2.0, 3.5, 2.0], 50.0),                 # one stalled epoch of four
    ([2.0, 2.9, 2.0, 2.0, 3.1, 2.0, 2.6], 50.0),  # three stalled of seven
    ([2.0, 4.0], 100.0 / 3.0),                    # two readings: their mean
])
def test_train_samples_per_s_is_the_median_over_epochs(epoch_seconds, want):
    """A neighbour's burst on a shared host stalls an epoch or two; the
    median of the epochs' seconds leaves it out, a total would not."""
    sys.path.insert(0, REPO)
    try:
        from benchmark.runners.train_fit import samples_per_s
    finally:
        sys.path.remove(REPO)
    assert samples_per_s(epoch_seconds, 100) == pytest.approx(want)
