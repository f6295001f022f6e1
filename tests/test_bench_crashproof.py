"""bench.py must always end stdout with one parseable JSON line, even
when the accelerator backend cannot initialize -- and must then exit
non-zero, so a missing device can never read as a finished run."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(platforms: str):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = platforms
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env)
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert lines, f"no stdout at all; stderr: {out.stderr[-500:]}"
    return out, json.loads(lines[-1])  # the driver's parse contract


def test_backend_unavailable_still_emits_final_json_line():
    out, final = _run_bench("bogus")     # force backend init failure
    assert final == {"value": None, "error": "backend_unavailable"}
    assert out.returncode != 0
    assert "backend unavailable" in out.stderr


def test_unknown_device_kind_is_an_error_not_a_default_peak():
    """A device the peaks table does not know (here: the CPU) stops
    the bench before any phase runs; no assumed peak, no exit 0."""
    out, final = _run_bench("cpu")
    assert final["value"] is None
    assert "no peak FLOP/s recorded for device kind" in final["error"]
    assert out.returncode != 0
