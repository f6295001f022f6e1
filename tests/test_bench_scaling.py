"""Smoke the multichip harness: scaling efficiency (north-star #3),
the final-JSON-line + non-zero-exit failure contract, and the
sharded-serving A/B on the CPU host-device mesh (ISSUE-7)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env.update(extra)
    return env


def test_scaling_harness_outputs_json():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_scaling.py"),
         "--virtual", "4", "--per-device-batch", "256"],
        capture_output=True, text=True, timeout=540, cwd=REPO,
        env=_clean_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["metric"] == "scaling_efficiency"
    assert set(out["extras"]["efficiency"]) == {"1", "2", "4"}
    assert out["extras"]["efficiency"]["1"] == 1.0


def test_backend_unavailable_still_emits_final_json_line():
    """A backend that cannot initialize: a guaranteed parseable final
    line AND a non-zero exit (bench.py's established convention)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_scaling.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=_clean_env(JAX_PLATFORMS="bogus"))
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, f"no stdout at all; stderr: {proc.stderr[-500:]}"
    assert json.loads(lines[-1]) == {"value": None,
                                     "error": "backend_unavailable"}
    assert proc.returncode != 0
    assert "backend unavailable" in proc.stderr


def test_serving_shard_smoke_on_host_device_mesh():
    """The multichip SERVING measurement runs hardware-free: 8 virtual
    CPU devices, shard modes off + tp through the real pipelined
    engine, one JSON line with the (size x mode) table."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_scaling.py"),
         "--serving", "--virtual", "8", "--sizes", "small",
         "--modes", "off,tp", "--serving-requests", "300",
         "--windows", "1", "--matched-seconds", "1"],
        capture_output=True, text=True, timeout=540, cwd=REPO,
        env=_clean_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "serving_shard_ab"
    table = out["extras"]["table"]["small"]
    assert set(table) == {"off", "tp"}
    for mode in table.values():
        assert mode["rps"] > 0
    assert out["extras"]["n_devices"] == 8
