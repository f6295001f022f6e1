"""Compile the main path's attention kernels for a TPU v5e that is
described, not attached: the chip's own compiler runs here and refuses
what it would refuse there (a slice off the tiling, too much VMEM), at
no chip time. Nothing executes -- a pass says the kernel compiles, not
that it is right or fast.

The code under test asks ``jax.default_backend()``, which is the CPU
here, so the tests steer it: the kernels' interpret switch is patched
off and the dispatcher is told its platform.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from analytics_zoo_tpu.ops import attention, pallas_attention


@pytest.fixture(scope="module")
def one_chip():
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _compiled_kernels_no_persistent_cache(monkeypatch):
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    monkeypatch.setattr(attention, "_platform", lambda q: "tpu")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one: the next
    # run would warn on every entry and compile again
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _qkv(shape, sharding):
    return (jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                 sharding=sharding),) * 3


def _scalar(out):
    return jnp.sum(out.astype(jnp.float32))


@pytest.mark.parametrize("shape,causal", [
    ((48, 12, 384, 64), False),    # BERT-base b48 / L384
    ((4, 12, 2048, 64), True),     # long context, d64
    ((2, 16, 4096, 128), True),    # long context, d128
])
def test_owned_flash_compiles_forward_and_backward(one_chip, shape,
                                                   causal):
    def attn(q, k, v):
        return pallas_attention.pallas_flash_attention_fwd(q, k, v,
                                                           causal)

    args = _qkv(shape, one_chip)
    fwd = jax.jit(attn).lower(*args).compile().as_text()
    assert fwd.count("tpu_custom_call") == 1
    bwd = jax.jit(jax.grad(lambda q, k, v: _scalar(attn(q, k, v)),
                           argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    assert bwd.count("tpu_custom_call") == 3  # fwd+lse, dq, dk/dv


def test_dispatcher_padding_mask_path_compiles(one_chip):
    """L1024 with a key-padding mask: the public dispatcher's
    segment-id path (the stock kernel), forward and backward."""
    b, h, l, d = 2, 12, 1024, 64
    args = _qkv((b, h, l, d), one_chip) + (
        jax.ShapeDtypeStruct((b, l), jnp.int32, sharding=one_chip),)

    def attn(q, k, v, kpm):
        return attention.dot_product_attention(q, k, v,
                                               key_padding_mask=kpm)

    fwd = jax.jit(attn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in fwd
    bwd = jax.jit(jax.grad(
        lambda q, k, v, kpm: _scalar(attn(q, k, v, kpm)),
        argnums=(0, 1, 2))).lower(*args).compile().as_text()
    assert "tpu_custom_call" in bwd
