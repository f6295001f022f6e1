"""Activations whose backward is worth owning.

``gelu_exact`` is the erf GELU of BERT and torch with a stored
derivative. Left to autodiff, XLA keeps only the pre-activation and
re-derives the erf polynomial inside every consumer: on a v5e the three
``ffn_out`` matmul fusions of a BERT layer each waited ~0.45 ms on
float32 vector work and ran at 38 % of the MXU (docs/kernels.md,
"Exact GELU"). Here erf is evaluated once per element per step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@jax.custom_vjp
def gelu_exact(x):
    """``jax.nn.gelu(x, approximate=False)``, bit for bit."""
    return jax.nn.gelu(x, approximate=False)


def _made_once(g, d):
    """``g`` and ``d`` behind one ``optimization_barrier``: without it
    XLA re-derives both from ``x`` inside their consumers, which is the
    cost this module removes.

    A 16-bit pair crosses as one 32-bit word. XLA:TPU grows a matmul's
    epilogue towards one root: given two leaves it makes the second in
    an element-wise pass of its own (0.24 ms a BERT layer); one word is
    one root, and each consumer's fusion takes its half apart."""
    if g.dtype.itemsize != 2:
        d, g = lax.optimization_barrier((d, g))
        return g, d

    def bits(t):
        return lax.bitcast_convert_type(t, jnp.uint16).astype(jnp.uint32)

    def half(w):
        return lax.bitcast_convert_type(w.astype(jnp.uint16), g.dtype)

    word = lax.optimization_barrier((bits(g) << 16) | bits(d))
    return half(word >> 16), half(word)


def _gelu_exact_fwd(x):
    with jax.named_scope("ffn_gelu"):
        # jax.nn.gelu's own expression, so the value under grad is the
        # primal's; erfc(-x/sqrt 2) = 2 Phi(x) also gives the derivative
        two_cdf = lax.erfc(-x * np.sqrt(0.5).astype(x.dtype))
        g = 0.5 * x * two_cdf
        # gelu'(x) = Phi(x) + x phi(x): float32 arithmetic, rounded once
        # to the dtype every other saved activation has
        wide = jnp.promote_types(x.dtype, jnp.float32)
        xw = x.astype(wide)
        pdf = jnp.exp(-0.5 * xw * xw) * np.asarray(
            1.0 / np.sqrt(2.0 * np.pi), wide)
        d = (0.5 * two_cdf.astype(wide) + xw * pdf).astype(x.dtype)
        return _made_once(g, d)


def _gelu_exact_bwd(d, ct):
    with jax.named_scope("ffn_gelu"):
        return (ct * d,)


gelu_exact.defvjp(_gelu_exact_fwd, _gelu_exact_bwd)
