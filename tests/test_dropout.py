"""The package's one dropout (``ops/dropout.py``, ISSUE 39): flax's
mathematics, its bits wherever no pure data mesh of several devices is
traced or the key is threefry's, and for an ``rbg`` key under one each
device drawing its own rows' bits."""

import pathlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.obs.metrics import get_registry
from analytics_zoo_tpu.ops.dropout import Dropout
from analytics_zoo_tpu.parallel.mesh import create_mesh, traced_under

GAUGE = "zoo_ops_dropout_sites_items"


def _mesh(axes):
    n = int(np.prod(list(axes.values())))
    return create_mesh(axes, devices=jax.devices()[:n])


def _apply(module, x, key, mesh=None):
    def f(x, key):
        if mesh is None:
            return module.apply({}, x, rngs={"dropout": key})
        with traced_under(mesh):
            return module.apply({}, x, rngs={"dropout": key})

    return jax.jit(f)


def _rbg(seed=0):
    return jax.random.key(seed, impl="rbg")


def _u32_sizes(hlo_text: str) -> list:
    return [int(np.prod([int(d) for d in dims.split(",")]))
            for dims in re.findall(r"u32\[([\d,]+)\]", hlo_text)]


def _sites_of(module: str) -> dict:
    family = get_registry().snapshot().get(GAUGE) or {"values": {}}
    return {path: family["values"].get(f"module={module},path={path}")
            for path in ("shard_local", "global")}


@pytest.mark.parametrize("axes,rows,impl,path,local_bits", [
    ({"data": 4}, 128, "rbg", "shard_local", True),
    ({"data": 2, "model": 2}, 128, "rbg", "global", False),
    ({"data": 4}, 126, "rbg", "global", False),
    # XLA splits threefry's counter-based bits by rows itself
    ({"data": 4}, 128, "threefry2x32", "global", True),
])
def test_bits_are_drawn_where_the_rows_live(axes, rows, impl, path,
                                            local_bits):
    """XLA's partitioner leaves ``RngBitGenerator`` whole: the global
    draw of an ``rbg`` key generates every row's bits on every device
    (and slices where the rows are sharded). The shard-local draw
    generates 32 rows'."""
    mesh = _mesh(axes)
    spec = P("data") if rows % 4 == 0 else P()
    x = jax.device_put(jnp.ones((rows, 64, 64)), NamedSharding(mesh, spec))
    key = jax.random.key(0, impl=impl)
    text = _apply(Dropout(0.1, deterministic=False), x, key,
                  mesh).lower(x, key).compile().as_text()
    assert _sites_of("") == {"shard_local": float(path == "shard_local"),
                             "global": float(path == "global")}
    assert max(_u32_sizes(text)) == (rows // 4 if local_bits else rows) \
        * 64 * 64
    sliced = re.search(r"u32\[[\d,]+\]\S* dynamic-slice\(", text)
    assert bool(sliced) == (impl == "rbg" and axes.get("model", 1) > 1)


def test_each_shard_draws_its_own_mask():
    mesh = _mesh({"data": 4})
    x = jax.device_put(jnp.ones((128, 64, 64)), NamedSharding(mesh, P("data")))
    out = np.asarray(_apply(Dropout(0.1, deterministic=False), x, _rbg(),
                            mesh)(x, _rbg()))
    kept = (out != 0).reshape(4, 32, 64, 64)
    for i in range(4):
        assert abs(1 - kept[i].mean() - 0.1) < 0.02
        for j in range(i):
            assert (kept[i] != kept[j]).any()
    # what is kept is scaled by 1 / (1 - rate)
    np.testing.assert_allclose(out[out != 0], 1 / 0.9, rtol=1e-6)


@pytest.mark.parametrize("train", [True, False])
@pytest.mark.parametrize("rate", [0.0, 0.1, 1.0])
@pytest.mark.parametrize("impl", ["threefry2x32", "rbg"])
def test_without_a_mesh_it_is_flax_to_the_bit(impl, rate, train):
    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 16, 32)),
                    jnp.bfloat16)
    key = jax.random.key(3, impl=impl)

    def run(cls):
        return np.asarray(_apply(cls(rate, deterministic=not train), x,
                                 key)(x, key).astype(jnp.float32))

    np.testing.assert_array_equal(run(Dropout), run(nn.Dropout))


@pytest.mark.parametrize("axes", [None, {"data": 1}, {"data": 4}])
def test_same_key_same_mask_and_the_gradient(axes):
    mesh = _mesh(axes) if axes else None
    x = jnp.asarray(np.random.default_rng(2).normal(size=(16, 8, 32)),
                    jnp.float32)
    module = Dropout(0.25, deterministic=False)

    def loss(x, key):
        if mesh is None:
            return jnp.sum(module.apply({}, x, rngs={"dropout": key}))
        with traced_under(mesh):
            return jnp.sum(module.apply({}, x, rngs={"dropout": key}))

    f = jax.jit(jax.value_and_grad(loss))
    (v1, g1), (v2, g2) = f(x, _rbg(5)), f(x, _rbg(5))
    assert v1 == v2
    g = np.asarray(g1)
    np.testing.assert_array_equal(g, np.asarray(g2))
    kept = np.asarray(_apply(module, x, _rbg(5), mesh)(x, _rbg(5))) != 0
    np.testing.assert_allclose(g[kept], 1 / 0.75, rtol=1e-6)
    assert (g[~kept] == 0).all() and 0.15 < 1 - kept.mean() < 0.35


def _sites(path: str, prefix: str) -> int:
    family = get_registry().snapshot().get(GAUGE) or {"values": {}}
    return int(sum(v for k, v in family["values"].items()
                   if k.startswith(f"module={prefix}")
                   and k.endswith(f"path={path}")))


def test_bert_base_counts_its_26_sites():
    """Embeddings, two a layer x 12, the head: shard-local when traced
    under ``data=4``, global when no mesh is traced."""
    from analytics_zoo_tpu.models.text.bert_squad import BERTForSQuAD

    module = BERTForSQuAD(vocab=64, hidden_size=32, n_block=12, n_head=2,
                          intermediate_size=64, max_position_len=16)
    x = {"input_ids": jnp.zeros((8, 16), jnp.int32)}
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)

    def trace():  # a new function each time: JAX keeps traces by function
        jax.eval_shape(lambda params, x, key: module.apply(
            params, x, train=True, rngs={"dropout": key}), params, x, _rbg())

    with traced_under(_mesh({"data": 4})):
        trace()
    assert _sites("shard_local", "squad/") == 26
    assert _sites("global", "squad/") == 0
    trace()
    assert _sites("shard_local", "squad/") == 0
    assert _sites("global", "squad/") == 26


def test_no_other_dropout_in_the_package():
    package = pathlib.Path(__file__).parent.parent / "analytics_zoo_tpu"
    found = [str(p.relative_to(package)) for p in package.rglob("*.py")
             if "nn.Dropout(" in p.read_text()]
    assert found == []
