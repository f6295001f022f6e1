"""Test harness configuration.

Every test runs the *real* SPMD code path on a virtual 8-device CPU mesh --
the TPU-native analog of the reference's pattern of booting a real
``local[4]`` SparkContext + BigDL engine in every test
(ref: pyzoo/test/zoo/pipeline/utils/test_utils.py:20-60, ZooTestCase).

The suite always runs on the CPU backend, whatever the shell says:
``JAX_PLATFORMS`` and ``XLA_FLAGS`` are both read at the first JAX
backend initialization, so they are set here before jax is imported.
"""

import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {devs}"
    return devs


@pytest.fixture()
def tmp_ckpt_dir(tmp_path):
    d = tmp_path / "ckpt"
    d.mkdir()
    return str(d)


@pytest.fixture
def compiled_anew():
    """The step program of the 8-device CPU mesh holds ``while`` loops
    with collectives in their bodies (GSPMD gathers the sharded tokens
    inside the expert layer's bounded passes). Compiled, it runs; loaded
    from the persistent compilation cache, XLA:CPU's executable
    deadlocks in the first of them (jaxlib 0.9.0; the TPU loads its own
    fine). So no cache here, read or written."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
