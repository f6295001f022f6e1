"""CPU rehearsal of ``chip_smoke.py``: the refusal IS the behaviour
under test (no accelerator -> non-zero exit, no result line, the
platform it found named), and every phase function runs end to end
at a tiny size so a wrong path, argument or check is found here and
not on chip time. Kernel presence, real widths and every timing are
the chip run's business."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# BERT at a width the CPU trains in seconds
TINY_BERT = dict(vocab=100, hidden_size=32, n_block=1, n_head=2,
                 intermediate_size=64)


def test_refuses_to_run_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "platform is 'cpu', not 'tpu'" in out.stderr


@pytest.fixture(scope="module")
def watch():
    return chip_smoke.CompileWatch()


def test_device_phase_fails_where_memory_stats_are_missing(watch):
    # the CPU backend reports none: on the chip that is an error
    with pytest.raises(chip_smoke.SmokeFailure, match="memory_stats"):
        chip_smoke.phase_device(watch)


def test_kernels_phase_numerics(watch):
    out = chip_smoke.run_phase(
        "kernels", watch, chip_smoke.phase_kernels, seqs=(128,),
        batch=2, heads=2, expect_kernel=False)
    assert [c["case"] for c in out["cases"]] == [
        "L128", "L128_padmask", "L128_causal", "L128_causal_padmask"]


def test_kernels_phase_demands_a_compiled_kernel(watch):
    # on the CPU the dispatcher takes the einsum path: exactly what
    # the chip run must never silently accept
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="no tpu_custom_call"):
        chip_smoke.phase_kernels(watch, seqs=(128,), batch=1, heads=1)


def test_train_phase_compiles_once_and_learns(watch):
    out = chip_smoke.run_phase(
        "train", watch, chip_smoke.phase_train, batch=8, seq=32,
        steps=8, **TINY_BERT)
    assert out["train_step_compiles"] == 1
    assert out["compiles_in_pass2"] == 0
    assert out["loss_pass2"] < out["loss_pass1"]


def test_serve_phase_answers_every_request_once(watch):
    out = chip_smoke.run_phase(
        "serve", watch, chip_smoke.phase_serve, n_requests=6, batch=2,
        image_size=32, class_num=10)
    assert out["answered_once"] == 6 and out["live_compiles"] == 0


def test_generate_phase_is_token_exact(watch):
    out = chip_smoke.run_phase(
        "generate", watch, chip_smoke.phase_generate, n_prompts=3,
        max_tokens=8, max_len=64)
    assert out["token_mismatches"] == 0 and out["live_compiles"] == 0


def test_mesh_phase_layouts_agree_on_virtual_devices(watch):
    out = chip_smoke.run_phase(
        "mesh", watch, chip_smoke.phase_mesh, batch=8, seq=32, steps=2,
        **TINY_BERT)
    layouts = out["layouts"]
    assert layouts["one_device"]["all_reduces"] == 0
    assert layouts["data4"]["batch_shard_shape"] == [2, 32]
    assert layouts["data2_model2"]["sharded_params"] > 0
