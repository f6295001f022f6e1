"""Dropout whose random bits are drawn where its rows live.

``flax.linen.Dropout`` draws its mask with ``jax.random.bernoulli`` over
the input's whole shape. In a step partitioned over a data mesh that
shape is the *global* batch. XLA's SPMD partitioner splits threefry's
counter-based bits by rows, but leaves ``RngBitGenerator`` -- the
generator of the ``rbg`` keys ``Estimator`` trains with on the TPU --
whole: every device generates the bits of every row and then
``dynamic-slice``s out its own (BERT-base at 4 x 32 rows of 384: 26
masks of 151 MB of u32 a step on each chip, where it keeps 38 MB of
each).

``dropout`` keeps flax's mathematics -- keep with probability
``1 - rate``, scale what is kept by ``1 / (1 - rate)``, zeros elsewhere
-- and chooses where to draw from what the trace can observe: where the
key is an ``rbg`` family key and ``parallel.mesh.traced_mesh()`` is a
pure data mesh of several devices (every axis but ``DATA_AXIS``, the
axis the ``Estimator`` shards its batch over, of size 1) whose size
divides the rows, the draw runs inside a ``shard_map`` with the rows
over that axis and the key replicated, folded with the device's index
on the axis: each device draws its own rows' bits and nothing is
sliced. Anywhere else (a threefry key, no traced mesh, one device, a
model or sequence axis, rows that do not divide) it runs flax's code,
so the bits and the compiled program are flax's. The draw is under
``jax.named_scope("dropout_bits")``.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax, random
from jax.sharding import Mesh, PartitionSpec as P

from analytics_zoo_tpu.obs.metrics import get_registry

_M_SITES = get_registry().gauge(
    "zoo_ops_dropout_sites_items",
    "Dropout modules that drew a mask, by where the bits were drawn: "
    "shard_local (each device its own rows' bits) or global (the whole "
    "input's bits, flax's draw); 1 on the path the module took when "
    "the step was last traced, 0 on the other",
    ("module", "path"))


def shard_local_mesh(rows: int, rng) -> Optional[Mesh]:
    """The traced mesh where each device should draw its own ``rows``'
    bits from ``rng``: a pure data mesh of several devices whose size
    divides the rows, for a key whose generator XLA leaves whole;
    else ``None``."""
    # imported here: ``parallel/`` imports the layers that import this
    from analytics_zoo_tpu.parallel.mesh import (
        DATA_AXIS, mesh_axis_size, traced_mesh)

    mesh = traced_mesh()
    if (mesh is None or not jnp.issubdtype(rng.dtype, jax.dtypes.prng_key)
            or str(random.key_impl(rng)) not in ("rbg", "unsafe_rbg")):
        return None
    n = mesh_axis_size(mesh, DATA_AXIS)
    return mesh if 1 < n == mesh.size and rows % n == 0 else None


def dropout(x, rate: float, rng, mesh: Optional[Mesh] = None):
    """``x`` with each entry kept with probability ``1 - rate`` and
    scaled by ``1 / (1 - rate)``, zeros elsewhere (``0 < rate < 1``).
    With a ``mesh`` from ``shard_local_mesh`` each device draws the
    bits of its own rows of ``x`` from ``rng`` folded with its index on
    the data axis; without one, the bits of all of ``x`` from ``rng``,
    as ``flax.linen.Dropout`` does."""
    keep = 1.0 - rate

    def draw(x, rng):
        with jax.named_scope("dropout_bits"):
            mask = random.bernoulli(rng, p=keep, shape=x.shape)
        return lax.select(mask, x / keep, jnp.zeros_like(x))

    if mesh is None:
        return draw(x, rng)
    from analytics_zoo_tpu.parallel.mesh import DATA_AXIS, shard_map

    def local(x, rng):
        return draw(x, random.fold_in(rng, lax.axis_index(DATA_AXIS)))

    rows = P(DATA_AXIS)
    return shard_map(local, mesh, in_specs=(rows, P()),
                     out_specs=rows)(x, rng)


class Dropout(nn.Module):
    """``flax.linen.Dropout`` (its early returns and module names, so
    its ``make_rng`` keys) over ``dropout``: the one dropout of the
    package. Sets ``zoo_ops_dropout_sites_items`` while traced."""

    rate: float
    deterministic: bool

    @nn.compact
    def __call__(self, inputs):
        if self.rate == 0.0 or self.deterministic:
            return inputs
        # no mask at all: a mask of rate 1 would scale by 1 / 0
        if self.rate == 1.0:
            return jnp.zeros_like(inputs)
        rng = self.make_rng("dropout")
        mesh = shard_local_mesh(inputs.shape[0], rng) if inputs.ndim else None
        module = "/".join(self.path)
        _M_SITES.labels(module=module, path="shard_local").set(
            float(mesh is not None))
        _M_SITES.labels(module=module, path="global").set(float(mesh is None))
        return dropout(inputs, self.rate, rng, mesh)
