"""Tests for the layer-library gap fill (VERDICT round-1 item 10):
Masking, MaxoutDense, GaussianDropout/Sampler, SpatialDropout,
LocallyConnected, ResizeBilinear, LRN2D, SparseEmbedding/Dense,
ConvLSTM3D."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.keras.layers import (
    ConvLSTM3D, GaussianDropout, GaussianSampler, LocallyConnected1D,
    LocallyConnected2D, LRN2D, Masking, MaxoutDense, ResizeBilinear,
    SparseDense, SparseEmbedding, SpatialDropout1D, SpatialDropout2D,
    SpatialDropout3D)
from tests.test_keras import apply_layer


class TestMasking:
    def test_zeroes_fully_masked_timesteps(self):
        x = np.ones((2, 4, 3), np.float32)
        x[0, 1] = -1.0  # fully masked step
        x[1, 2, 0] = -1.0  # partially -1: NOT masked
        out = apply_layer(Masking(mask_value=-1.0), x)
        assert (out[0, 1] == 0).all()
        assert (out[1, 2] == x[1, 2]).all()
        assert (out[0, 0] == 1).all()


class TestMaxoutDense:
    def test_shape_and_max_property(self):
        x = np.random.RandomState(0).randn(4, 6).astype(np.float32)
        out = apply_layer(MaxoutDense(5, nb_feature=3), x)
        assert out.shape == (4, 5)

    def test_is_max_of_pieces(self):
        import jax
        import jax.numpy as jnp

        layer = MaxoutDense(2, nb_feature=4)
        m = layer.build()
        x = jnp.asarray(np.random.RandomState(1).randn(3, 5),
                        jnp.float32)
        v = m.init(jax.random.PRNGKey(0), x)
        out = m.apply(v, x)
        # recompute manually from the underlying dense
        flat = jax.tree_util.tree_leaves(v)
        dense_out = None
        for leaf in flat:
            if getattr(leaf, "ndim", 0) == 2:
                dense_out = x @ leaf
        for leaf in flat:
            if getattr(leaf, "ndim", 0) == 1:
                dense_out = dense_out + leaf
        manual = jnp.max(dense_out.reshape(3, 4, 2), axis=1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(manual),
                                   atol=1e-6)


class TestNoiseLayers:
    def test_gaussian_dropout_train_vs_eval(self):
        x = np.ones((64, 32), np.float32)
        eval_out = apply_layer(GaussianDropout(0.3), x)
        np.testing.assert_array_equal(eval_out, x)
        train_out = apply_layer(GaussianDropout(0.3), x, train=True)
        assert not np.allclose(train_out, x)
        # multiplicative noise is mean-1: sample mean stays near 1
        assert abs(train_out.mean() - 1.0) < 0.05

    @pytest.mark.parametrize("cls,shape", [
        (SpatialDropout1D, (8, 10, 16)),
        (SpatialDropout2D, (8, 6, 6, 16)),
        (SpatialDropout3D, (4, 3, 4, 4, 16)),
    ])
    def test_spatial_dropout_drops_whole_channels(self, cls, shape):
        x = np.ones(shape, np.float32)
        out = apply_layer(cls(0.5), x, train=True)
        # every channel is either fully zero or fully scaled per sample
        flat = out.reshape(shape[0], -1, shape[-1])
        for b in range(shape[0]):
            for c in range(shape[-1]):
                col = flat[b, :, c]
                assert (col == 0).all() or (col == col[0]).all()
        assert (out == 0).any()
        np.testing.assert_array_equal(apply_layer(cls(0.5), x), x)

    def test_gaussian_sampler_mean_at_eval(self):
        import jax
        import jax.numpy as jnp

        layer = GaussianSampler()
        m = layer.build()
        mean = jnp.ones((4, 3))
        log_var = jnp.zeros((4, 3))
        v = m.init({"params": jax.random.PRNGKey(0),
                    "dropout": jax.random.PRNGKey(0)}, [mean, log_var])
        out_eval = m.apply(v, [mean, log_var])
        np.testing.assert_array_equal(np.asarray(out_eval),
                                      np.ones((4, 3)))
        out_train = m.apply(v, [mean, log_var], train=True,
                            rngs={"dropout": jax.random.PRNGKey(1)})
        assert not np.allclose(np.asarray(out_train), 1.0)


class TestLocallyConnected:
    def test_1d_shape(self):
        x = np.random.RandomState(0).randn(2, 10, 3).astype(np.float32)
        out = apply_layer(LocallyConnected1D(5, 3), x)
        assert out.shape == (2, 8, 5)

    def test_2d_matches_manual_patches(self):
        import jax
        import jax.numpy as jnp

        rng = np.random.RandomState(1)
        x = rng.randn(2, 5, 6, 3).astype(np.float32)
        layer = LocallyConnected2D(4, 2, 3)
        m = layer.build()
        v = m.init(jax.random.PRNGKey(0), jnp.asarray(x))
        out = np.asarray(m.apply(v, jnp.asarray(x)))
        assert out.shape == (2, 4, 4, 4)
        # manual check of one output position against the einsum
        leaves = {l.shape: l for l in jax.tree_util.tree_leaves(v)}
        w = [l for l in jax.tree_util.tree_leaves(v) if l.ndim == 3][0]
        patch = x[:, 1:3, 2:5, :].reshape(2, -1)  # position (1, 2)
        pos = 1 * 4 + 2
        manual = patch @ np.asarray(w)[pos]
        bias = [l for l in jax.tree_util.tree_leaves(v)
                if l.ndim == 2][0]
        manual = manual + np.asarray(bias)[pos]
        np.testing.assert_allclose(out[:, 1, 2], manual, atol=1e-5)

    def test_no_weight_sharing(self):
        # a delta at one position must not affect other positions'
        # response the way shared conv would
        x = np.zeros((1, 6, 3), np.float32)
        out_zero = apply_layer(LocallyConnected1D(1, 3, bias=False), x)
        np.testing.assert_allclose(out_zero, 0, atol=1e-7)


class TestResizeAndLRN:
    def test_resize_bilinear(self):
        x = np.random.RandomState(0).randn(2, 8, 8, 3).astype(np.float32)
        out = apply_layer(ResizeBilinear(16, 12), x)
        assert out.shape == (2, 16, 12, 3)

    def test_lrn_shape_identity_when_alpha_zero(self):
        x = np.random.RandomState(1).randn(1, 4, 4, 8).astype(np.float32)
        out = apply_layer(LRN2D(alpha=0.0, k=1.0), x)
        np.testing.assert_allclose(out, x, atol=1e-6)


class TestSparse:
    def test_sparse_embedding_sum_ignores_padding(self):
        import jax
        import jax.numpy as jnp

        layer = SparseEmbedding(10, 4, combiner="sum")
        m = layer.build()
        ids = jnp.asarray([[1, 2, 0, 0], [3, 0, 0, 0]], jnp.int32)
        v = m.init(jax.random.PRNGKey(0), ids)
        out = np.asarray(m.apply(v, ids))
        table = np.asarray(
            [l for l in jax.tree_util.tree_leaves(v) if l.ndim == 2][0])
        np.testing.assert_allclose(out[0], table[1] + table[2],
                                   atol=1e-6)
        np.testing.assert_allclose(out[1], table[3], atol=1e-6)

    def test_sparse_embedding_mean(self):
        import jax
        import jax.numpy as jnp

        layer = SparseEmbedding(10, 4, combiner="mean")
        m = layer.build()
        ids = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
        v = m.init(jax.random.PRNGKey(0), ids)
        out = np.asarray(m.apply(v, ids))
        table = np.asarray(
            [l for l in jax.tree_util.tree_leaves(v) if l.ndim == 2][0])
        np.testing.assert_allclose(out[0], (table[1] + table[2]) / 2,
                                   atol=1e-6)

    def test_sparse_dense_trains(self):
        x = np.random.RandomState(0).randn(8, 6).astype(np.float32)
        out = apply_layer(SparseDense(3, activation="relu"), x)
        assert out.shape == (8, 3) and (out >= 0).all()


class TestConvLSTM3D:
    def test_shapes(self):
        x = np.random.RandomState(0).randn(
            2, 3, 4, 4, 4, 2).astype(np.float32)
        out = apply_layer(ConvLSTM3D(5, 3), x)
        assert out.shape == (2, 4, 4, 4, 5)
        out_seq = apply_layer(ConvLSTM3D(5, 3, return_sequences=True), x)
        assert out_seq.shape == (2, 3, 4, 4, 4, 5)


class TestTableOps:
    """MM / SelectTable / SplitTensor (VERDICT round-3 item 8; ref:
    InternalMM.scala, SelectTable.scala, SplitTensor.scala)."""

    def test_mm_2d_golden(self):
        from analytics_zoo_tpu.keras.layers import MM

        rng = np.random.RandomState(0)
        a = rng.randn(4, 6).astype(np.float32)
        b = rng.randn(6, 3).astype(np.float32)
        m = MM().build()
        out = np.asarray(m.apply({}, [a, b]))
        np.testing.assert_allclose(out, a @ b, rtol=1e-5, atol=1e-6)

    def test_mm_3d_transposes(self):
        from analytics_zoo_tpu.keras.layers import MM

        rng = np.random.RandomState(1)
        a = rng.randn(2, 5, 4).astype(np.float32)
        b = rng.randn(2, 5, 3).astype(np.float32)
        m = MM(trans_a=True).build()
        out = np.asarray(m.apply({}, [a, b]))
        want = np.einsum("bka,bkc->bac", a, b)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
        m2 = MM(trans_b=True).build()
        at, bt = a.transpose(0, 2, 1), b.transpose(0, 2, 1)
        out2 = np.asarray(m2.apply({}, [at, bt]))
        want2 = np.einsum("bak,bck->bac", at, bt)
        np.testing.assert_allclose(out2, want2, rtol=1e-5, atol=1e-5)

    def test_mm_rejects_bad_rank(self):
        from analytics_zoo_tpu.keras.layers import MM

        with pytest.raises(ValueError, match="both be 2D"):
            MM().build().apply({}, [np.ones((2, 2, 2, 2), np.float32),
                                    np.ones((2, 2), np.float32)])

    def test_split_select_roundtrip(self):
        from analytics_zoo_tpu.keras.layers import SelectTable, SplitTensor

        x = np.arange(24, dtype=np.float32).reshape(2, 12)
        table = SplitTensor(dimension=0, num=3).build().apply({}, x)
        assert isinstance(table, tuple) and len(table) == 3
        got = np.asarray(SelectTable(1).build().apply({}, table))
        np.testing.assert_allclose(got, x[:, 4:8])

    def test_split_rejects_indivisible(self):
        from analytics_zoo_tpu.keras.layers import SplitTensor

        with pytest.raises(ValueError, match="divisible"):
            SplitTensor(dimension=0, num=5).build().apply(
                {}, np.ones((2, 12), np.float32))

    def test_graph_split_mm_topology(self):
        """A branching table graph: split an input, matmul the halves
        -- the topology the reference builds with SplitTensor +
        SelectTable + InternalMM."""
        from analytics_zoo_tpu.keras.engine import Input, Model
        from analytics_zoo_tpu.keras.layers import (
            MM, SelectTable, SplitTensor)

        inp = Input((4, 6))
        table = SplitTensor(dimension=1, num=2)(inp)
        left = SelectTable(0)(table)
        right = SelectTable(1)(table)
        out = MM(trans_b=True)([left, right])
        model = Model(input=inp, output=out)
        x = np.random.RandomState(2).randn(8, 4, 6).astype(np.float32)
        preds = model.predict(x, batch_size=8)
        want = np.einsum("bik,bjk->bij", x[:, :, :3], x[:, :, 3:])
        np.testing.assert_allclose(preds, want, rtol=1e-4, atol=1e-5)
