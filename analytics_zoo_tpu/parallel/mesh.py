"""Device-mesh construction.

Replaces the reference's cluster-topology discovery (BigDL ``Engine.init``
node/core discovery, ref: zoo/.../common/NNContext.scala:134-150, and the
five runtimes of SURVEY.md section 2.3) with a single concept: an N-d
``jax.sharding.Mesh`` whose axes are the parallelism dimensions
(data / fsdp / tensor / sequence / pipeline / expert).

On multi-host TPU pods, ``create_mesh`` builds a *hybrid* mesh so that the
fastest-varying axes ride ICI within a slice and only the outermost axis
crosses DCN -- the layout recommended by the scaling playbook.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical axis names used across the framework.
DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
TENSOR_AXIS = "model"
SEQUENCE_AXIS = "seq"
PIPELINE_AXIS = "pipe"
EXPERT_AXIS = "expert"


def create_mesh(
    axes: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh from an ordered ``{axis_name: size}`` mapping.

    An axis size of ``-1`` (at most one) is inferred from the device count.
    With no ``axes``, returns a 1-d data-parallel mesh over all devices.

    On multi-process (multi-host) runs, uses
    ``mesh_utils.create_hybrid_device_mesh`` so the innermost axes map to
    ICI and the outer product to DCN.
    """
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    if not axes:
        axes = {DATA_AXIS: n}
    names = tuple(axes.keys())
    sizes = [int(s) for s in axes.values()]
    n_infer = sum(1 for s in sizes if s == -1)
    if n_infer > 1:
        raise ValueError(f"at most one axis may be -1, got {axes}")
    if n_infer == 1:
        known = int(np.prod([s for s in sizes if s != -1]))
        if known == 0 or n % known != 0:
            raise ValueError(
                f"cannot infer axis: {n} devices not divisible by {known}")
        sizes = [n // known if s == -1 else s for s in sizes]
    total = int(np.prod(sizes))
    if total != n:
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {total} devices, have {n}")

    if jax.process_count() > 1 and devices == jax.devices():
        # hybrid ICI x DCN layout: split each axis into a DCN (across hosts)
        # and ICI (within host) factor.
        from jax.experimental import mesh_utils

        n_hosts = jax.process_count()
        dcn = _factor_over_hosts(sizes, n_hosts)
        ici = [s // d for s, d in zip(sizes, dcn)]
        try:
            dev_array = mesh_utils.create_hybrid_device_mesh(
                ici, dcn, devices=devices)
        except ValueError:
            if devices[0].platform == "tpu":
                # on real pods a factoring error is a misconfiguration;
                # a topology-ignorant fallback would silently route
                # ICI-heavy axes over DCN
                raise
            # no slice topology (multi-process CPU testing): a
            # process-major reshape keeps host boundaries on the
            # outermost axis factors, good enough off-TPU
            ordered = sorted(devices,
                             key=lambda d: (d.process_index, d.id))
            dev_array = np.asarray(ordered).reshape(sizes)
        return Mesh(dev_array, names)

    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, names)


def _factor_over_hosts(sizes: Sequence[int], n_hosts: int) -> list:
    """Greedily assign the host (DCN) factor to the outermost axes."""
    remaining = n_hosts
    dcn = []
    for s in sizes:
        g = int(np.gcd(s, remaining))
        dcn.append(g)
        remaining //= g
    if remaining != 1:
        raise ValueError(
            f"cannot factor {n_hosts} hosts over mesh sizes {list(sizes)}")
    return dcn


def default_mesh() -> Mesh:
    """The context mesh if a ZooContext is live, else a fresh DP mesh."""
    from analytics_zoo_tpu.common.context import ZooContext

    ctx = ZooContext.get()
    if ctx is not None:
        return ctx.mesh
    return create_mesh()


_TRACED = threading.local()


@contextlib.contextmanager
def traced_under(mesh: Mesh):
    """Declares, for the ``with`` block, the mesh the program being
    traced will be partitioned over: the ``Estimator`` wraps its model
    calls in it, so a layer that must say how an opaque kernel call is
    sharded (``traced_mesh``) learns the mesh of *this* program, not
    the context's."""
    before = traced_mesh()
    _TRACED.mesh = mesh
    try:
        yield
    finally:
        _TRACED.mesh = before


def traced_mesh() -> Optional[Mesh]:
    """The mesh declared by the innermost ``traced_under``, else
    ``None``: nobody has said what the program is partitioned over."""
    return getattr(_TRACED, "mesh", None)


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.devices.shape[mesh.axis_names.index(name)]


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off -- bodies with
    per-shard divergent values (dropout keys, quantization scales) are
    the norm in this package. Every ``parallel/`` and serving
    shard_map routes through here."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def config_axis(role: str, fallback: Optional[str] = None) -> str:
    """Canonical mesh-axis name for a parallelism *role* -- the
    ``zoo.mesh.axis.<role>`` config family (roles: data, model,
    sequence, pipeline, expert). Call sites take an ``axis`` argument
    and default it through here, so a deployment that renames an axis
    (e.g. a hybrid mesh calling its tensor axis ``"tp"``) sets one
    config key instead of threading the name through every recipe.
    Unknown roles fall back to ``fallback`` (default: the role
    itself)."""
    from analytics_zoo_tpu.common.config import get_config

    return str(get_config().get("zoo.mesh.axis." + role,
                                fallback if fallback is not None
                                else role))
