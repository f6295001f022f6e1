"""Transformer/BERT layer tests + pallas kernel CPU-fallback checks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.layers import BERTModule, TransformerModule
from analytics_zoo_tpu.ops.attention import dot_product_attention


class TestAttentionOp:
    def test_matches_naive(self):
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(2, 3, 8, 16), jnp.float32)
        k = jnp.asarray(rng.randn(2, 3, 8, 16), jnp.float32)
        v = jnp.asarray(rng.randn(2, 3, 8, 16), jnp.float32)
        out = dot_product_attention(q, k, v)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(16)
        ref = jnp.einsum("bhqk,bhkd->bhqd",
                         jax.nn.softmax(logits, -1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_mask_blocks_attention(self):
        q = jnp.ones((1, 1, 2, 4))
        k = jnp.ones((1, 1, 3, 4))
        v = jnp.asarray(np.arange(12, dtype=np.float32)
                        .reshape(1, 1, 3, 4))
        mask = jnp.asarray([[[[1, 1, 0], [1, 1, 0]]]])  # 3rd key masked
        out = dot_product_attention(q, k, v, mask=mask)
        # keys 0 and 1 equally weighted -> mean of first two value rows
        want = (np.arange(4) + np.arange(4, 8)) / 2
        np.testing.assert_allclose(np.asarray(out)[0, 0, 0], want,
                                   atol=1e-5)


class TestTransformer:
    def test_decoder_stack_shapes_and_causality(self):
        m = TransformerModule(vocab=50, seq_len=12, hidden_size=32,
                              n_head=4, n_block=2, hidden_dropout=0.0,
                              attn_dropout=0.0)
        ids = np.arange(24).reshape(2, 12) % 50
        variables = m.init(jax.random.PRNGKey(0), ids)
        out = m.apply(variables, ids)
        assert out.shape == (2, 12, 32)
        # causality: changing a late token must not affect early outputs
        ids2 = ids.copy()
        ids2[:, -1] = (ids2[:, -1] + 1) % 50
        out2 = m.apply(variables, ids2)
        np.testing.assert_allclose(np.asarray(out[:, :6]),
                                   np.asarray(out2[:, :6]), atol=1e-5)
        assert not np.allclose(np.asarray(out[:, -1]),
                               np.asarray(out2[:, -1]))

    def test_bert_outputs_and_mask(self):
        m = BERTModule(vocab=60, hidden_size=32, n_block=2, n_head=4,
                       intermediate_size=64, max_position_len=16,
                       hidden_dropout=0.0, attn_dropout=0.0)
        batch = {
            "input_ids": np.arange(20).reshape(2, 10) % 60,
            "token_type_ids": np.zeros((2, 10), np.int32),
            "attention_mask": np.concatenate(
                [np.ones((2, 6), np.int32), np.zeros((2, 4), np.int32)],
                axis=1),
        }
        variables = m.init(jax.random.PRNGKey(0), batch)
        seq, pooled = m.apply(variables, batch)
        assert seq.shape == (2, 10, 32)
        assert pooled.shape == (2, 32)
        # masked positions must not influence kept positions: changing a
        # masked token's id leaves real-token outputs unchanged
        batch2 = {k: (v.copy() if hasattr(v, "copy") else v)
                  for k, v in batch.items()}
        batch2["input_ids"][:, 8] = (batch2["input_ids"][:, 8] + 7) % 60
        seq2, _ = m.apply(variables, batch2)
        np.testing.assert_allclose(np.asarray(seq[:, :6]),
                                   np.asarray(seq2[:, :6]), atol=1e-5)

    def test_bert_finetune_classification(self):
        """Tiny BERT fine-tune through the Estimator (north-star #4's
        shape, tiny scale)."""
        import flax.linen as nn

        from analytics_zoo_tpu.learn import Estimator, Adam

        class Classifier(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False):
                _, pooled = BERTModule(
                    vocab=40, hidden_size=16, n_block=1, n_head=2,
                    intermediate_size=32, max_position_len=8,
                    name="bert")(x, train=train)
                return nn.Dense(2)(pooled)

        rng = np.random.RandomState(0)
        ids = rng.randint(0, 40, (128, 8)).astype(np.int32)
        y = (ids[:, 0] > 20).astype(np.int32)
        est = Estimator(Classifier(),
                        loss="sparse_categorical_crossentropy",
                        optimizer=Adam(3e-3), metrics=["accuracy"])
        hist = est.fit(({"input_ids": ids}, y), batch_size=32, epochs=5)
        assert hist[-1]["loss"] < hist[0]["loss"]
        res = est.evaluate(({"input_ids": ids}, y), batch_size=32)
        assert res["accuracy"] > 0.8


class TestPallasKernel:
    """The hand-written flash kernel runs in pallas interpret mode on CPU,
    so its online-softmax logic is exercised by the normal test suite."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("d", [64, 128])  # 64 = BERT-base heads
    def test_kernel_matches_reference(self, causal, d):
        from analytics_zoo_tpu.ops import (
            pallas_flash_attention_fwd, reference_attention)

        rng = np.random.RandomState(0)
        b, h, l = 1, 2, 256
        q = jnp.asarray(rng.randn(b, h, l, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, h, l, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, h, l, d), jnp.float32)
        out = pallas_flash_attention_fwd(q, k, v, causal)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    @pytest.mark.parametrize("lq,lk", [(128, 384), (256, 384)])
    def test_kernel_cross_length_causal_matches_reference(self, lq, lk):
        # causal diagonal must align bottom-right (tril k=lk-lq) exactly
        # like the jnp reference path, so both dispatch paths agree
        from analytics_zoo_tpu.ops import (
            pallas_flash_attention_fwd, reference_attention)

        rng = np.random.RandomState(2)
        b, h, d = 1, 2, 128
        q = jnp.asarray(rng.randn(b, h, lq, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, h, lk, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, h, lk, d), jnp.float32)
        out = pallas_flash_attention_fwd(q, k, v, True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_kernel_rejects_causal_lq_gt_lk(self):
        from analytics_zoo_tpu.ops import pallas_flash_attention_fwd

        q = jnp.zeros((1, 1, 256, 128), jnp.float32)
        k = jnp.zeros((1, 1, 128, 128), jnp.float32)
        with pytest.raises(ValueError, match="len\\(q\\)"):
            pallas_flash_attention_fwd(q, k, k, True)

    def test_kernel_grad_finite(self):
        from analytics_zoo_tpu.ops import pallas_flash_attention_fwd

        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(1, 1, 128, 128), jnp.float32)
        g = jax.grad(lambda t: pallas_flash_attention_fwd(
            t, q, q, True).sum())(q)
        assert bool(jnp.isfinite(g).all())

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("d", [64, 128])  # 64 = BERT-base heads
    def test_flash_backward_matches_reference_grads(self, causal, d):
        # the blockwise dq/dk/dv kernels must match grads through the
        # dense jnp path (golden numerics for the flash backward)
        from analytics_zoo_tpu.ops import (
            pallas_flash_attention_fwd, reference_attention)

        rng = np.random.RandomState(3)
        b, h, l = 2, 2, 256
        q = jnp.asarray(rng.randn(b, h, l, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, h, l, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, h, l, d), jnp.float32)
        ct = jnp.asarray(rng.randn(b, h, l, d), jnp.float32)

        def loss_flash(q, k, v):
            return (pallas_flash_attention_fwd(q, k, v, causal) * ct).sum()

        def loss_ref(q, k, v):
            return (reference_attention(q, k, v, causal=causal) * ct).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gr), atol=2e-4,
                err_msg=f"d{name} mismatch")

    def test_flash_backward_long_context_1024_blocks(self):
        # the d<=64 / L>=2048 backward runs 1024-blocks (_bwd_blocks);
        # grads through that geometry must still match the dense path
        from analytics_zoo_tpu.ops import (
            pallas_flash_attention_fwd, reference_attention)
        from analytics_zoo_tpu.ops.pallas_attention import _bwd_blocks

        assert _bwd_blocks(2048, 2048, 64, None) == (1024, 1024)  # under test
        assert _bwd_blocks(1024, 1024, 64, None) == (512, 512)  # pipelining
        assert _bwd_blocks(1024, 2048, 64, None) == (512, 512)  # either side
        assert _bwd_blocks(2048, 2048, 128, None) == (512, 512)
        # d >= 128: 1024 from 8k on, and never under a window
        assert _bwd_blocks(8192, 8192, 128, None) == (1024, 1024)
        assert _bwd_blocks(8192, 8192, 192, None) == (1024, 1024)
        assert _bwd_blocks(8192, 8192, 128, 2048) == (512, 512)
        assert _bwd_blocks(4096, 8192, 128, None) == (512, 512)
        assert _bwd_blocks(8192 + 128, 8192 + 128, 128, None) == (640, 640)
        rng = np.random.RandomState(5)
        b, h, l, d = 1, 1, 2048, 64
        q = jnp.asarray(rng.randn(b, h, l, d) * 0.2, jnp.float32)
        k = jnp.asarray(rng.randn(b, h, l, d) * 0.2, jnp.float32)
        v = jnp.asarray(rng.randn(b, h, l, d) * 0.2, jnp.float32)

        def f(fn):
            return jax.grad(
                lambda a, b_, c: fn(a, b_, c).sum(), argnums=(0, 1, 2)
            )(q, k, v)

        g_flash = f(lambda a, b_, c: pallas_flash_attention_fwd(
            a, b_, c, False))
        g_ref = f(lambda a, b_, c: reference_attention(a, b_, c))
        for gf, gr in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                       atol=2e-4)

    def test_backward_path_is_a_function_of_the_shapes(self):
        # one kernel while its whole-sequence accumulators fit the
        # budget beside the kernel, two block-wise kernels past it:
        # (l, lk, d, d_k, d_v, itemsize); no option chooses
        from analytics_zoo_tpu.ops.pallas_attention import (
            FUSED_BWD_VMEM_BUDGET, _fused_bwd_vmem_bytes,
            flash_backward_path)

        assert flash_backward_path(8192, 8192, 128, 128, 128, 2) == "fused"
        assert flash_backward_path(8192, 8192, 128, 128, 128, 2,
                                   2048) == "fused"
        assert flash_backward_path(8192, 8192, 192, 128, 128, 2) == "fused"
        assert flash_backward_path(16384, 16384, 128, 128, 128, 2) == "fused"
        assert flash_backward_path(32768, 32768, 128, 128, 128, 2) == "split"
        assert flash_backward_path(16384, 16384, 128, 128, 128, 4) == "split"
        assert flash_backward_path(16384, 16384, 192, 128, 128, 2) == "split"
        assert flash_backward_path(384, 384, 64, 64, 64, 2) == "fused"
        # Trinity-Mini's window layer at 512^2: 3 x 4 MiB of accumulators,
        # 3 x 2 x 2 MiB of output blocks, tiles and intermediates;
        # Moonlight's 192 columns take two 128-lane tiles
        trinity = _fused_bwd_vmem_bytes(8192, 8192, 128, 128, 128, 2,
                                        512, 512)
        moonlight = _fused_bwd_vmem_bytes(8192, 8192, 192, 128, 128, 2,
                                          1024, 1024)
        assert 30 * 2 ** 20 <= trinity <= 34 * 2 ** 20
        assert 70 * 2 ** 20 <= moonlight <= 76 * 2 ** 20
        assert moonlight < FUSED_BWD_VMEM_BUDGET <= 100 * 2 ** 20
        # blocks the caller names count: the intermediates grow with them
        assert flash_backward_path(16384, 16384, 128, 128, 128, 4, None,
                                   256, 256) == "fused"
        assert (_fused_bwd_vmem_bytes(8192, 8192, 128, 128, 128, 2, 1024,
                                      1024) - trinity) > 12e6

    @pytest.mark.parametrize("case", [
        # (causal, window, lq, lk, heads, kv heads, d, dtype)
        (True, None, 512, 512, 2, 2, 128, "float32"),
        (True, 100, 512, 512, 2, 2, 128, "float32"),
        (True, None, 256, 512, 2, 2, 128, "float32"),   # Lq < Lk
        (True, 300, 256, 512, 2, 1, 64, "float32"),
        (False, None, 512, 512, 2, 2, 64, "float32"),
        (False, None, 256, 512, 2, 2, 128, "float32"),
        (True, None, 512, 512, 8, 1, 128, "float32"),   # group 8
        (True, 100, 512, 512, 8, 1, 64, "float32"),
        (True, None, 512, 512, 2, 2, 128, "bfloat16"),
        (True, 100, 512, 512, 8, 1, 128, "bfloat16"),
        (False, None, 512, 512, 8, 1, 128, "bfloat16"),
    ], ids=lambda c: "-".join(str(x) for x in c))
    @pytest.mark.parametrize("path", ["fused", "split"])
    def test_backward_accumulates_over_blocks(self, monkeypatch, case,
                                              path):
        # blocks of 128 at L512: dQ accumulates over up to four
        # kv-blocks while dK/dV accumulate over the q-blocks and the
        # group's heads; the one kernel and (the budget set to nothing)
        # the two kernels, each against reference_attention's gradients
        from analytics_zoo_tpu.ops import (
            pallas_attention, pallas_flash_attention_fwd,
            reference_attention)

        causal, window, lq, lk, h, h_kv, d, dtype = case
        if path == "split":
            monkeypatch.setattr(pallas_attention, "FUSED_BWD_VMEM_BUDGET", 0)
        assert pallas_attention.flash_backward_path(
            lq, lk, d, d, d, jnp.dtype(dtype).itemsize, window, 128,
            128) == path
        rng = np.random.RandomState(7)
        q, k, v, ct = (jnp.asarray(rng.randn(*shape), dtype) for shape in (
            (1, h, lq, d), (1, h_kv, lk, d), (1, h_kv, lk, d),
            (1, h, lq, d)))

        def loss(attend, *args):
            return jnp.sum(attend(*args).astype(jnp.float32)
                           * ct.astype(jnp.float32))

        got = jax.grad(lambda *a: loss(
            lambda q, k, v: pallas_flash_attention_fwd(
                q, k, v, causal, None, 128, 128, window), *a),
            argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: loss(
            lambda q, k, v: reference_attention(
                q, k, v, causal=causal, window=window), *a),
            argnums=(0, 1, 2))(*(a.astype(jnp.float32) for a in (q, k, v)))
        for g, w, name in zip(got, want, ("dq", "dk", "dv")):
            assert g.shape == w.shape and g.dtype == jnp.dtype(dtype), name
            if dtype == "float32":
                np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4,
                                           err_msg=name)
            else:
                g, w = np.asarray(g, np.float32), np.asarray(w)
                assert (np.linalg.norm(g - w) / np.linalg.norm(w)
                        < 1.5e-2), name

    def test_flash_backward_cross_length_grads(self):
        from analytics_zoo_tpu.ops import (
            pallas_flash_attention_fwd, reference_attention)

        rng = np.random.RandomState(4)
        b, h, lq, lk, d = 1, 2, 128, 384, 128
        q = jnp.asarray(rng.randn(b, h, lq, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, h, lk, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, h, lk, d), jnp.float32)

        def f(fn):
            return jax.grad(
                lambda a, b_, c: fn(a, b_, c).sum(), argnums=(0, 1, 2)
            )(q, k, v)

        g_flash = f(lambda a, b_, c: pallas_flash_attention_fwd(
            a, b_, c, True))
        g_ref = f(lambda a, b_, c: reference_attention(
            a, b_, c, causal=True))
        for gf, gr in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                       atol=2e-4)


class TestLoadWeightsFreshModel:
    def test_keras_load_weights_without_build(self, tmp_path):
        from analytics_zoo_tpu.keras import Sequential
        from analytics_zoo_tpu.keras.layers import Dense

        x = np.random.RandomState(0).randn(64, 4).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.int32)
        m = Sequential([Dense(8, activation="relu"), Dense(2)])
        m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
        m.fit(x, y, batch_size=32, nb_epoch=1)
        before = m.predict(x, batch_size=32)
        m.save_weights(str(tmp_path / "w"))

        m2 = Sequential([Dense(8, activation="relu"), Dense(2)])
        m2.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
        m2.load_weights(str(tmp_path / "w"))  # no fit/predict before
        after = m2.predict(x, batch_size=32)
        np.testing.assert_allclose(before, after, atol=1e-5)
