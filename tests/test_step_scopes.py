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
ATTENTION_SCOPES = ("attention_flash", "attention_flash_short",
                    "attention_stock_pallas",
                    "attention_einsum", "attention_reference",
                    "attention_flash_window", "attention_einsum_window",
                    "attention_reference_window")
MOE_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
              "moe_shared")


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


def _without_program_scopes(monkeypatch,
                            scopes=PROGRAM_SCOPES + ATTENTION_SCOPES):
    real = jax.named_scope

    def named_scope(name):
        if name in scopes:
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
                     dropout_rate: float = 0.0, window=None, **arrays):
    """The dispatcher's StableHLO with locations, lowered for the TPU
    from here: the kernels' paths are chosen off the CPU only, and
    lowering (unlike compiling) needs no chip."""
    if on_tpu:
        monkeypatch.setattr(attention, "_platform", lambda q: "tpu")
    qkv = jax.ShapeDtypeStruct((1, 2, length, 64), jnp.bfloat16)

    def attend(q, k, v, arrays):
        return attention.dot_product_attention(
            q, k, v, dropout_rate=dropout_rate, window=window,
            causal=window is not None, **arrays)

    return jax.jit(attend).trace(qkv, qkv, qkv, arrays).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)


@pytest.mark.parametrize("scope,on_tpu,length,kwargs", [
    ("attention_flash", True, 1024, {}),
    ("attention_stock_pallas", True, 1024,
     {"key_padding_mask": jax.ShapeDtypeStruct((1, 1024), jnp.int32)}),
    # BERT at L384: short rows of heads of 64 have a kernel of their own
    # on the chip (PR 36); with a mask they keep the einsum path
    ("attention_flash_short", True, 384, {}),
    ("attention_einsum", True, 384,
     {"key_padding_mask": jax.ShapeDtypeStruct((1, 384), jnp.int32)}),
    ("attention_einsum", False, 1024, {}),
    ("attention_reference", True, 1024,
     {"dropout_rate": 0.1,
      "dropout_rng": jax.ShapeDtypeStruct((2,), jnp.uint32)}),
    # a window call adds its component, so a trace separates the kinds
    ("attention_flash_window", True, 1024, {"window": 256}),
    ("attention_einsum_window", False, 1024, {"window": 256}),
])
def test_attention_path_names_itself(monkeypatch, scope, on_tpu, length,
                                     kwargs):
    text = _lowered_for_tpu(monkeypatch, on_tpu, length, **kwargs)
    found = set(re.findall(r"/(attention_[a-z_]+)/", text))
    assert found & set(ATTENTION_SCOPES) == {scope}


# The rule itself, as a table. Expected paths are read off the parent's
# dispatcher under its default, ``auto``, setting (156435e,
# ``ops/attention.py:139-164``), not off ``attention_path``. Columns:
# platform, Lq, Lk, head_dim, query heads, KV heads, what is present.
@pytest.mark.parametrize("path,platform,lq,lk,d,hq,hkv,present", [
    # the gate is inclusive: 512 is still short. Since PR 36 plain
    # self-attention with heads of 64 has the short-row kernels there
    ("flash_short", "tpu", 512, 512, 64, 12, 12, ()),
    ("flash_short", "tpu", 384, 384, 64, 12, 12, ()),
    ("flash_short", "tpu", 128, 128, 64, 2, 2, ()),
    # ... and every neighbouring case keeps the answer it had
    ("einsum", "tpu", 384, 384, 64, 12, 12, ("causal",)),
    ("einsum", "tpu", 384, 384, 64, 12, 12, ("causal", "window")),
    ("einsum", "tpu", 384, 384, 64, 12, 12, ("key_padding_mask",)),
    ("einsum", "tpu", 384, 384, 64, 12, 12, ("mask",)),
    ("reference", "tpu", 384, 384, 64, 12, 12, ("dropout",)),
    ("einsum", "tpu", 384, 384, 64, 12, 12, ("k_shared",)),
    ("einsum", "tpu", 384, 384, 128, 12, 12, ()),
    ("einsum", "tpu", 384, 384, 32, 12, 12, ()),
    ("einsum", "tpu", 128, 384, 64, 12, 12, ()),
    ("einsum", "tpu", 320, 320, 64, 12, 12, ()),
    ("einsum", "tpu", 384, 384, 64, 12, 4, ()),
    ("einsum", "tpu", 384, 384, 64, 3, 3, ()),      # two heads share a tile
    ("einsum", "cpu", 384, 384, 64, 12, 12, ()),
    ("flash", "tpu", 640, 640, 64, 12, 12, ()),
    # the longer side decides
    ("flash", "tpu", 128, 1024, 64, 12, 12, ()),
    # the owned kernel is bottom-right aligned: cross-length causal is its own
    ("flash", "tpu", 512, 1024, 128, 8, 8, ("causal",)),
    # Trinity-Mini's two layer kinds at 8k
    ("flash", "tpu", 8192, 8192, 128, 32, 4, ("causal", "window")),
    ("flash", "tpu", 8192, 8192, 128, 32, 4, ("causal",)),
    # no kernel takes a length that is not a multiple of 128
    ("einsum", "tpu", 1000, 1000, 64, 12, 12, ()),
    ("einsum", "tpu", 1024, 1000, 64, 12, 12, ()),
    # head sizes the owned kernel refuses: the stock one up to 128
    ("stock_pallas", "tpu", 1024, 1024, 96, 12, 12, ()),
    ("einsum", "tpu", 1024, 1024, 160, 12, 12, ()),
    # key-padding masks ride the stock kernel's segment ids, which know
    # no grouped heads, no cross-length causal offset and no window
    ("stock_pallas", "tpu", 1024, 1024, 64, 12, 12,
     ("key_padding_mask", "causal")),
    ("einsum", "tpu", 1024, 1024, 64, 8, 2, ("key_padding_mask",)),
    ("einsum", "tpu", 512, 1024, 64, 12, 12, ("key_padding_mask", "causal")),
    ("einsum", "tpu", 1024, 1024, 64, 12, 12,
     ("key_padding_mask", "causal", "window")),
    # an arbitrary mask materialises the scores at any length
    ("einsum", "tpu", 8192, 8192, 128, 32, 4, ("mask",)),
    # dropout is the reference's, with or without an rng, on any platform
    ("reference", "tpu", 1024, 1024, 64, 12, 12, ("dropout",)),
    ("reference", "cpu", 128, 128, 64, 12, 12, ("dropout",)),
    # the CPU compiles no kernel, whatever the shape
    ("einsum", "cpu", 8192, 8192, 128, 32, 4, ("causal", "window")),
    ("einsum", "cpu", 1024, 1024, 64, 12, 12, ("key_padding_mask",)),
])
def test_attention_path_rule(path, platform, lq, lk, d, hq, hkv, present):
    assert attention.attention_path(
        platform, lq, lk, d, hq, hkv,
        **{name: True for name in present}) == path


def test_ops_import_nothing_from_common():
    """``ops/`` is the lowest layer: what a kernel's dispatch needs it
    is handed by the caller or reads off the shapes, never out of the
    process-wide config."""
    import ast
    import pathlib

    ops = pathlib.Path(attention.__file__).parent
    for path in sorted(ops.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [
                    f"{node.module}.{a.name}" for a in node.names]
            assert not any(
                n.startswith("analytics_zoo_tpu.common") for n in names), (
                f"{path.name}:{node.lineno} imports {names}")


# ------------------------------------------------------------------ #
# the sparse decoder's scopes, and what this must not move           #
# ------------------------------------------------------------------ #
def _decoder_step_text() -> str:
    from analytics_zoo_tpu.learn.optim import AdamWeightDecay
    from analytics_zoo_tpu.models.text import SparseDecoderLM

    model = SparseDecoderLM(
        vocab=64, hidden_size=32,
        layer_types=["sliding_attention", "full_attention"],
        n_dense_layers=1, n_head=2, n_kv_head=1, head_dim=16, window=4,
        dense_width=48, expert_width=16, n_routed=8, n_held=4, top_k=2,
        route_scale=2.0)
    model.compile(optimizer=AdamWeightDecay(lr=1e-3))
    est = model.estimator
    x = {"input_ids": np.zeros((2, 8), np.int32)}
    est._ensure_built(x)
    step = jax.jit(lambda *args: est._step_math(*args))
    return step.lower(est.variables, est.opt_state, x,
                      np.zeros((2, 8), np.int32),
                      jax.random.PRNGKey(0)).compile().as_text()


def test_decoder_scopes_change_metadata_only(fresh_compiles, monkeypatch):
    with_scopes = _decoder_step_text()
    names = _op_names(with_scopes)
    for scope in MOE_SCOPES + ("attention_einsum_window",
                               "attention_einsum"):
        assert any(f"/{scope}/" in n or f"/{scope})" in n or
                   n.endswith("/" + scope) for n in names), scope
    # forward, and backward with the rematerialised forward under it
    assert any("jvp(SparseDecoderModule)/layer_1/moe/moe_experts" in n
               for n in names)
    assert any("transpose(jvp(SparseDecoderModule))" in n
               and "moe_experts" in n for n in names)
    # ``loss`` stays: an instruction inside a jitted helper takes its
    # NAME (not its work) from the enclosing ``jvp(loss)``
    _without_program_scopes(monkeypatch, MOE_SCOPES + ATTENTION_SCOPES)
    without = _decoder_step_text()
    scoped = re.compile(r"[/(](%s)[/)]" % "|".join(
        MOE_SCOPES + ATTENTION_SCOPES))
    assert any(scoped.search(n) for n in names)
    assert not any(scoped.search(n) for n in _op_names(without))
    assert _strip_metadata(with_scopes) == _strip_metadata(without)


# sha256 of the stripped, optimised HLO of a tiny BERT-SQuAD train step
# on this suite's CPU backend, taken on the parent of PR 27 (c8141dc)
# with the JAX below: the window / grouped-head changes to the
# dispatcher and the counters hook in ``fit`` leave BERT's program as
# it was. A new JAX makes new HLO: the check then skips.
BERT_STEP = ("0.9.0", "1eb9862ff882963846db3e8b59397c71"
                      "6505aa567c92db42cf647c066fa52b58")


def test_bert_step_program_is_what_it_was(fresh_compiles):
    import hashlib

    from analytics_zoo_tpu.learn.optim import AdamWeightDecay
    from analytics_zoo_tpu.models.text.bert_squad import BERTSQuAD

    if jax.__version__ != BERT_STEP[0]:
        pytest.skip(f"recorded with jax {BERT_STEP[0]}")
    model = BERTSQuAD(vocab=128, hidden_size=32, n_block=2, n_head=2,
                      intermediate_size=64, max_position_len=64,
                      dtype="bfloat16")
    model.compile(optimizer=AdamWeightDecay(lr=1e-4))
    est = model.estimator
    x = {"input_ids": np.zeros((4, 16), np.int32)}
    est._ensure_built(x)
    text = jax.jit(lambda *args: est._step_math(*args)).lower(
        est.variables, est.opt_state, x, np.zeros((4, 2), np.int32),
        jax.random.PRNGKey(0)).compile().as_text()
    assert "attention_einsum" in text
    assert hashlib.sha256(_strip_metadata(text).encode()).hexdigest() \
        == BERT_STEP[1]


def _bert_one_chip_step_text() -> str:
    from analytics_zoo_tpu.learn.optim import AdamWeightDecay
    from analytics_zoo_tpu.models.text.bert_squad import BERTSQuAD
    from analytics_zoo_tpu.parallel.mesh import create_mesh

    model = BERTSQuAD(vocab=128, hidden_size=32, n_block=2, n_head=2,
                      intermediate_size=64, max_position_len=64,
                      dtype="bfloat16")
    model.compile(optimizer=AdamWeightDecay(lr=1e-4),
                  mesh=create_mesh(devices=jax.devices()[:1]))
    est = model.estimator
    x = {"input_ids": np.zeros((8, 16), np.int32)}
    est._ensure_built(x)
    return jax.jit(lambda *args: est._step_math(*args)).lower(
        est.variables, est.opt_state, x, np.zeros((8, 2), np.int32),
        jax.random.key(0, impl="rbg")).compile().as_text()


def test_one_chip_dropout_is_flax_dropout(fresh_compiles, monkeypatch):
    """On one chip the package's dropout (ISSUE 39) compiles to the
    program ``flax.linen.Dropout`` gave: its ``dropout_bits`` scope is
    metadata, and the rows' bits are drawn as flax draws them."""
    from analytics_zoo_tpu.keras.layers import transformer
    from analytics_zoo_tpu.models.text import bert_estimators

    ours = _bert_one_chip_step_text()
    assert "/dropout_bits/" in ours
    for site in (transformer, bert_estimators):
        monkeypatch.setattr(site, "Dropout", nn.Dropout)
    flax_s = _bert_one_chip_step_text()
    assert "/dropout_bits/" not in flax_s
    assert _strip_metadata(ours) == _strip_metadata(flax_s)


# The same pin for the two other programs the benchmark's cells run,
# both recorded on the parent of PR 29 (156435e), before the attention
# dispatcher lost its config keys and ``batch_norm()`` its sampled
# branch: an image classifier whose backbone goes through
# ``batch_norm()``, and the sparse decoder's step (window and full
# attention over grouped KV heads, the dropless expert layer). The
# decoder's was recorded again at PR 34, whose ``nn.remat`` keeps the
# layers' named values on the CPU path too (all but the kernel's two).
RESNET_STEP = ("0.9.0", "730eda3c954f4d916e67046572c6f2e1"
                        "a565c6751a7c592f64331af8971379fe")
DECODER_STEP = ("0.9.0", "80870ef4c66f9539a00d5a01e7c1a9ea"
                         "d8a12ec2db24de1e53b68f3a54e224df")


def _stripped_sha256(hlo_text: str) -> str:
    """Of the stripped text with every ``%name.N`` replaced by its rank
    of first appearance: XLA:CPU numbers a large module's instructions
    differently from one compile to the next (ResNet-18's step read
    ``%convert.660`` or ``%convert.662``), the program being the same."""
    import hashlib

    ranks = {}
    text = re.sub(r"%[\w.\-]+",
                  lambda m: ranks.setdefault(m.group(), f"%{len(ranks)}"),
                  _strip_metadata(hlo_text))
    return hashlib.sha256(text.encode()).hexdigest()


def test_resnet_step_program_is_what_it_was(fresh_compiles):
    from analytics_zoo_tpu.models import ImageClassifier

    if jax.__version__ != RESNET_STEP[0]:
        pytest.skip(f"recorded with jax {RESNET_STEP[0]}")
    model = ImageClassifier(class_num=4, backbone="resnet18", image_size=32,
                            dtype="bfloat16")
    model.compile(optimizer=optax.sgd(0.1, momentum=0.9))
    est = model.estimator
    x = np.zeros((4, 32, 32, 3), np.uint8)
    est._ensure_built(x)
    text = jax.jit(lambda *args: est._step_math(*args)).lower(
        est.variables, est.opt_state, x, np.zeros((4,), np.int32),
        jax.random.PRNGKey(0)).compile().as_text()
    assert "BatchNorm" in text
    assert _stripped_sha256(text) == RESNET_STEP[1]


def test_decoder_step_program_is_what_it_was(fresh_compiles):
    if jax.__version__ != DECODER_STEP[0]:
        pytest.skip(f"recorded with jax {DECODER_STEP[0]}")
    text = _decoder_step_text()
    assert "attention_einsum_window" in text
    assert _stripped_sha256(text) == DECODER_STEP[1]
