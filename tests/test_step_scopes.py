"""The train step names its device time (ISSUE 24): flax gives every
module's path, JAX marks forward ops ``jvp(...)`` and backward ops
``transpose(jvp(...))``, and the program scopes what no module names --
the optimizer update, the loss, the microbatch scan and the attention
path taken. A scope is ``op_name`` metadata and nothing else."""

import contextlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from analytics_zoo_tpu.learn.estimator import Estimator
from analytics_zoo_tpu.ops import attention

PROGRAM_SCOPES = ("optimizer", "loss", "grad_accum")
ATTENTION_SCOPES = ("attention_flash", "attention_stock_pallas",
                    "attention_einsum", "attention_reference")


@pytest.fixture()
def fresh_compiles():
    """Compile with the persistent cache off. Its key is taken after
    debug info is stripped (jax/_src/cache_key.py), so scope names are
    not part of it: a warm cache (another test's ``init_zoo_context``
    turns it on, at ``.xla_cache``) returns the executable of whichever
    tree wrote the entry, with that tree's ``op_name``s."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


class Net(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(8, name="hidden")(x))
        return nn.Dense(2, name="head")(x)


def _compiled_step_text(grad_accum_steps: int) -> str:
    """Optimised HLO of ``Estimator._step_math`` (the math both the
    per-step and the device-cached epoch program run)."""
    rng = np.random.default_rng(0)
    x = rng.random((8, 4), np.float32)
    y = rng.random((8, 2), np.float32)
    est = Estimator(Net(), loss=lambda p, t: jnp.mean((p - t) ** 2),
                    optimizer=optax.adam(1e-3),
                    grad_accum_steps=grad_accum_steps)
    est._ensure_built((x[:1],))
    step = jax.jit(lambda *args: est._step_math(*args))
    return step.lower(est.variables, est.opt_state, x, y,
                      jax.random.PRNGKey(0)).compile().as_text()


def _op_names(hlo_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


@pytest.mark.parametrize("grad_accum_steps", [1, 2])
def test_step_ops_carry_phase_and_scope(fresh_compiles, grad_accum_steps):
    names = _op_names(_compiled_step_text(grad_accum_steps))

    def some(part):
        return any(part in n for n in names)

    assert some("/optimizer/")
    # forward and backward, by JAX itself, with the module's path
    assert some("/jvp(Net)/hidden/dot_general")
    assert some("/transpose(jvp(Net))/hidden/")
    # a scope opened under value_and_grad comes out as the argument of
    # the transform: this is the form the benchmark's phase rule reads
    assert some("jvp(loss)/")
    assert some("transpose(jvp(loss))/")
    assert some("/grad_accum/") == (grad_accum_steps > 1)
    # no op of the update is named as forward or backward, or the reverse
    assert not any("/optimizer/" in n and "jvp(" in n for n in names)


def _without_program_scopes(monkeypatch):
    real = jax.named_scope

    def named_scope(name):
        if name in PROGRAM_SCOPES + ATTENTION_SCOPES:
            return contextlib.nullcontext()
        return real(name)

    monkeypatch.setattr(jax, "named_scope", named_scope)


def _strip_metadata(hlo_text: str) -> str:
    """Without each instruction's ``metadata={...}`` and without the
    module's tables of files, functions and stack frames that the
    metadata points into."""
    text = re.sub(r",?\s*metadata=\{[^{}]*\}", "", hlo_text)
    return re.sub(r"(?m)^(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:.+\n)*", "", text)


@pytest.mark.parametrize("grad_accum_steps", [1, 2])
def test_scopes_change_metadata_only(fresh_compiles, monkeypatch,
                                     grad_accum_steps):
    with_scopes = _compiled_step_text(grad_accum_steps)
    _without_program_scopes(monkeypatch)
    without = _compiled_step_text(grad_accum_steps)
    assert not any("/optimizer/" in n for n in _op_names(without))
    assert any("/optimizer/" in n for n in _op_names(with_scopes))
    assert _strip_metadata(with_scopes) == _strip_metadata(without)
    assert "metadata=" not in _strip_metadata(with_scopes)


def _lowered_for_tpu(monkeypatch, on_tpu: bool, length: int,
                     dropout_rate: float = 0.0, **arrays):
    """The dispatcher's StableHLO with locations, lowered for the TPU
    from here: the kernels' paths are chosen off the CPU only, and
    lowering (unlike compiling) needs no chip."""
    if on_tpu:
        monkeypatch.setattr(attention, "_platform", lambda q: "tpu")
    qkv = jax.ShapeDtypeStruct((1, 2, length, 64), jnp.bfloat16)

    def attend(q, k, v, arrays):
        return attention.dot_product_attention(
            q, k, v, dropout_rate=dropout_rate, **arrays)

    return jax.jit(attend).trace(qkv, qkv, qkv, arrays).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


@pytest.mark.parametrize("scope,on_tpu,length,kwargs", [
    ("attention_flash", True, 1024, {}),
    ("attention_stock_pallas", True, 1024,
     {"key_padding_mask": jax.ShapeDtypeStruct((1, 1024), jnp.int32)}),
    # BERT at L384: short sequences take the einsum path on the chip too
    ("attention_einsum", True, 384, {}),
    ("attention_einsum", False, 1024, {}),
    ("attention_reference", True, 1024,
     {"dropout_rate": 0.1,
      "dropout_rng": jax.ShapeDtypeStruct((2,), jnp.uint32)}),
])
def test_attention_path_names_itself(monkeypatch, scope, on_tpu, length,
                                     kwargs):
    text = _lowered_for_tpu(monkeypatch, on_tpu, length, **kwargs)
    found = set(re.findall(r"/(attention_[a-z_]+)/", text))
    assert found & set(ATTENTION_SCOPES) == {scope}
