"""Mixture-of-experts layers. Two modules, two routers:

``MoEFFN`` -- softmax top-k router, two-matrix experts with biases and
an activation (GELU by default), a sown load-balance loss. Its paths:

- **Dense path** (no mesh axis): every expert runs on every token and
  the top-k gate weights select -- exact, E/k times the needed work;
  the numeric reference for ``MoEFFN``'s two other paths (and for
  nothing else: ``DroplessExperts`` below is another layer, whose plain
  reference is ``benchmark/reference/trinity.py``).
- **Expert-parallel, broadcast layout** (``layout="broadcast"``):
  expert parameters shard over a mesh axis (one slice of experts per
  device). Each device computes ONLY its resident experts on the
  (replicated) token stream, gates zero out non-selected experts, and
  one ``psum`` over the expert axis merges contributions -- exact
  equality with the dense path by construction. Comm is a single psum
  of activations over ICI, but every expert still runs on every token:
  it shards expert MEMORY, not compute.
- **Expert-parallel, dispatch layout** (``layout="dispatch"``): the
  GShard/Switch all-to-all layout. Tokens shard over (data x expert)
  devices; each source device packs per-expert capacity buffers
  (``capacity_factor``; overflow tokens are DROPPED -- slot-major
  priority, first choices ahead of second), one ``all_to_all`` over
  the expert axis carries each buffer to the expert's home device,
  each expert runs on only its ~n*k/E routed tokens, and the inverse
  ``all_to_all`` + combine weights scatter results back. Compute AND
  memory scale 1/ep; kept tokens match the dense path exactly, dropped
  tokens contribute zero (the residual path carries them).

``MoEFFN``'s router is a standard softmax top-k with renormalized
gates and the switch-transformer load-balance auxiliary loss, sown into
the ``losses`` collection as ``moe_aux_loss`` (fetch with
``mutable=["losses"]`` and add it to the objective).

``DroplessExperts`` -- sigmoid router with a selection bias that no
gradient trains, normalised and scaled weights, SwiGLU experts without
biases, an optional shared expert; *told which experts it holds*. One
path: route over all ``n_routed`` experts, keep the assignments that
fall on the ``n_held`` held here, sort them by expert, run the SwiGLU
as grouped matrix products (``grouped_dot``), sum them back per token
under their weights. No capacity and no dropped token, at static
shapes: the sorted buffer has one row for every assignment that could
fall on a held expert (``tokens * min(top_k, n_held)``), and *how much
of it is touched is read on the device*. Every pass over it -- the row
gather in (``_spread``), ``silu(a) * b`` (``_gated``), the weighted
sum back per token (``_collect``), and their transposes -- is a loop
over tiles whose trip count is ``ceil(held / tile)``, ``held`` being
the step's count of assignments on held experts; the grouped products
visit only the row tiles of their groups. Rows past the last visited
tile are never written and never read (docs/kernels.md "Bounded
passes"; the counters ``moe_buffer_tiles_visited`` / ``moe_buffer_tiles``
say how much was visited). With every token on ``min(top_k, n_held)``
held experts the loops cover the whole buffer. With ``n_held ==
n_routed`` it is the whole layer; with fewer it is what one chip of an
expert-parallel group computes between the two exchanges, which are
not wired here.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from analytics_zoo_tpu.keras.activations import get as get_activation
from analytics_zoo_tpu.keras.layers.base import KerasLayer
from analytics_zoo_tpu.ops.dropout import Dropout


def resolve_expert_axis(value: Optional[str]) -> Optional[str]:
    """``"auto"`` -> the ``zoo.mesh.axis.expert`` config key; any other
    value (an explicit axis name, or None for the dense path) passes
    through unchanged."""
    if value == "auto":
        from analytics_zoo_tpu.parallel.mesh import config_axis

        return config_axis("expert")
    return value

__all__ = ["MoEFFN", "MoE", "MoETransformerBlock", "DroplessExperts",
           "SwiGLU"]


# SwiGLU's two pre-activations, named for ``jax.checkpoint`` policies
# (``save_only_these_names``): a caller that rematerialises the layer
# round this module and keeps them runs none of the three products a
# second time (the backward pass needs ``x W1`` and ``x W3`` themselves
# and rebuilds ``silu(.) * .`` from them). Outside such a policy a name
# is the identity.
SWIGLU_GATE_NAME = "swiglu_gate"
SWIGLU_UP_NAME = "swiglu_up"


class SwiGLU(nn.Module):
    """``(silu(x W1) * (x W3)) W2`` without biases."""

    width: int
    dtype: Any = jnp.float32
    kernel_init: Any = nn.linear.default_kernel_init

    @nn.compact
    def __call__(self, x):
        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype,
                            kernel_init=self.kernel_init, name=name)

        gate = checkpoint_name(dense(self.width, "w1")(x), SWIGLU_GATE_NAME)
        up = checkpoint_name(dense(self.width, "w3")(x), SWIGLU_UP_NAME)
        return dense(x.shape[-1], "w2")(nn.silu(gate) * up)


# rows of the sorted buffer in one tile of the grouped products' kernels
GROUPED_DOT_ROWS = 512
# widest contraction or column tile, and the most a contraction tile
# times a column tile may cover: the weights' gradient keeps that many
# float32 sums in VMEM beside its operands' tiles, and 1,024 x 1,408
# is 0.8 MB over what the chip's compiler allows a kernel
# (docs/kernels.md)
GROUPED_DOT_WIDEST = 1408
GROUPED_DOT_AREA = 1024 * 1024


def grouped_dot_tiling(k: int, n: int) -> tuple:
    """(rows, contraction, columns) tile of one grouped product
    [m, k] x [groups, k, n]: multiples of 128 that divide k and n, the
    pair that covers most within ``GROUPED_DOT_AREA`` -- (512, 1024,
    1024) at experts 1,024 wide under d 2,048; at 1,408 = 11 x 128 the
    whole width in one tile beside 512 of the other side, where a
    1,024 tile would have the kernels mask or pad 27 % of their second
    tile (docs/kernels.md). A width no multiple of 128 divides gets
    128 (the kernels mask the remainder)."""
    def tiles(width: int) -> list:
        return [t for t in range(128, GROUPED_DOT_WIDEST + 1, 128)
                if width % t == 0] or [128]

    tile_k, tile_n = max(
        ((a, b) for a in tiles(k) for b in tiles(n)
         if a * b <= GROUPED_DOT_AREA), key=lambda ab: (ab[0] * ab[1], ab))
    return GROUPED_DOT_ROWS, tile_k, tile_n


def _interpret() -> bool:
    """The attention kernels' switch: the CPU interprets (the tests' way
    in), every other backend compiles."""
    from analytics_zoo_tpu.ops import pallas_attention

    return pallas_attention._interpret()


@jax.custom_vjp
def _gmm(x, w, sizes):
    """``megablox``' grouped product with each pass tiled for its own
    shapes: the library's custom VJP hands the forward's tile to the
    two backward products, whose contraction and columns are other
    widths."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return gmm(x, w, sizes, x.dtype, grouped_dot_tiling(*w.shape[1:]),
               interpret=_interpret())


def _gmm_fwd(x, w, sizes):
    return _gmm(x, w, sizes), (x, w, sizes)


def _gmm_bwd(res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    x, w, sizes = res
    k, n = w.shape[1:]
    dx = gmm(g, w, sizes, x.dtype, grouped_dot_tiling(n, k),
             transpose_rhs=True, interpret=_interpret())
    dw = tgmm(x.swapaxes(0, 1), g, sizes, w.dtype,
              grouped_dot_tiling(k, n), None, w.shape[0],
              interpret=_interpret())
    return dx, dw, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_dot(x, w, sizes):
    """``x[rows of group e] @ w[e]`` for rows sorted by group: x [m, k],
    w [groups, k, n], ``sizes`` [groups] int32. Rows past ``sum(sizes)``
    are left undefined. Off the CPU the product is JAX's Pallas grouped
    matmul (``megablox``' ``gmm``, and ``gmm`` transposed and ``tgmm``
    backward, each tiled by ``grouped_dot_tiling`` -- through the
    library's own VJP where one tile serves all three, else through
    ``_gmm``'s: they visit only the row tiles that hold a group's rows,
    and their ``pallas_call`` keeps the caller's scope in ``op_name``);
    on the CPU, and for a row count the tile does not divide,
    ``jax.lax.ragged_dot``. On the TPU
    ``ragged_dot`` compiles to a kernel of XLA's own that is named
    ``ragged-dot-none`` whatever scope it was called under, so a device
    trace cannot attribute it (docs/kernels.md has both timings)."""
    from analytics_zoo_tpu.ops.attention import _platform

    if _platform(x) == "cpu" or x.shape[0] % GROUPED_DOT_ROWS:
        return jax.lax.ragged_dot(x, w, sizes)
    k, n = w.shape[1:]
    tiling = grouped_dot_tiling(k, n)
    if tiling == grouped_dot_tiling(n, k):
        # one tile serves the three passes (1,024-wide experts under
        # d 2,048): the library's own VJP runs exactly these kernels
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        # positional: the custom_vjp marks arguments 3, 4, 7, 8 static
        return gmm(x, w, sizes, x.dtype, tiling, None, None, False,
                   _interpret())
    return _gmm(x, w, sizes)


# rows of the sorted buffer that one trip of a bounded pass covers: a
# multiple of the grouped product's 512 (chosen on the chip,
# docs/kernels.md "Bounded passes")
BUFFER_TILE = 1024
# token-major places that one product of the slot sum reduces
SEGMENT_BLOCK = 256
# The passes below are jitted with both as static arguments: the four
# layers' forward, rematerialised forward and backward then share one
# trace of each (traced in place they added a tenth to the cell's warm
# set-up, PERF.md section 6), and every call keeps its own scope in
# ``op_name``.


def _empty(shape, dtype):
    """A buffer that nothing has written: the bounded passes fill the
    tiles up to the last held assignment and nothing reads the rest."""
    return jax.lax.empty(shape, dtype)


class _Plan(NamedTuple):
    """Where every held assignment stands, twice: sorted by expert (the
    buffer's rows ``0 .. held - 1``) and in token order (its *places*
    ``0 .. held - 1``, a token's assignments side by side). A slot is
    ``token * top_k + choice``."""

    held: jax.Array            # () how many assignments fall on held experts
    slot_of_row: jax.Array     # [rows]
    token_of_row: jax.Array    # [rows]
    slot_of_place: jax.Array   # [rows]; n * k from place ``held`` on
    token_of_place: jax.Array  # [rows]; n from place ``held`` on
    row_of_place: jax.Array    # [rows]
    first_place: jax.Array     # [n] a token's first place
    has_place: jax.Array       # [n] does it have one?


@functools.partial(jax.jit, static_argnames="rows")
def _plan(local, sizes, *, rows: int) -> _Plan:
    """``local`` [n, k]: each choice's index among the ``len(sizes)``
    held experts, or ``len(sizes)`` for an absent one. Two stable sorts
    of integers (cheap on the TPU, where a scatter of as many is not):
    the slots by expert give the rows, absent experts' last; the held
    rows by slot give the places."""
    n, k = local.shape
    held = jnp.sum(sizes)
    _, slot_of_row = jax.lax.sort(
        (local.ravel(), jnp.arange(n * k, dtype=jnp.int32)), num_keys=1)
    slot_of_row = slot_of_row[:rows]
    row = jnp.arange(rows, dtype=jnp.int32)
    slot_of_place, row_of_place = jax.lax.sort(
        (jnp.where(row < held, slot_of_row, n * k), row), num_keys=1)
    mine = jnp.sum(local < sizes.shape[0], axis=1, dtype=jnp.int32)
    return _Plan(held, slot_of_row, slot_of_row // k, slot_of_place,
                 slot_of_place // k, row_of_place, jnp.cumsum(mine) - mine,
                 mine > 0)


def _trips(held, tile: int):
    return (held + tile - 1) // tile


def _tiles(held, rows: int, tile: int, one_tile, out):
    """``one_tile(at, tile, filled, out)`` over the buffer's tiles of
    ``tile`` rows, up to the one that holds the last held assignment: a
    loop whose trip count is read on the device. (Where the tile does
    not divide the buffer the last one starts early and covers some
    rows a second time, with the same values.)"""
    tile = min(tile, rows)

    def body(i, out):
        at = jnp.minimum(i * tile, rows - tile)
        return one_tile(at, tile, (at + jnp.arange(tile) < held)[:, None],
                        out)

    return jax.lax.fori_loop(0, _trips(held, tile), body, out)


@functools.partial(jax.jit, static_argnames="tile")
def _spread(x, plan: _Plan, weights=None, y=None, *, tile: int):
    """[n, d] -> [rows, d]: row r takes its token's row of ``x``; the
    rows behind the last held assignment in its tile are zeros, the
    tiles behind it are not written. With ``weights`` [n, k] each row is
    scaled by its assignment's weight, and the second result [n, k] is
    each held assignment's product of that row of ``x`` with its row of
    ``y`` [rows, d] (the weight's gradient)."""
    rows, d = plan.slot_of_row.shape[0], x.shape[1]
    dots = (None if weights is None
            else jnp.zeros((weights.size,), jnp.float32))

    def one_tile(at, tile, filled, carry):
        out, dots = carry
        got = x[jax.lax.dynamic_slice(plan.token_of_row, (at,), (tile,))]
        if weights is not None:
            slots = jax.lax.dynamic_slice(plan.slot_of_row, (at,), (tile,))
            mine = jax.lax.dynamic_slice(y, (at, 0), (tile, d))
            # (an unfilled row's index is past the end, each its own)
            dots = dots.at[jnp.where(
                filled[:, 0], slots, dots.shape[0] + jnp.arange(tile))].set(
                jnp.sum(got.astype(jnp.float32) * mine.astype(jnp.float32),
                        axis=-1), mode="drop", unique_indices=True)
            got = got * weights.ravel()[slots][:, None].astype(x.dtype)
        return jax.lax.dynamic_update_slice(
            out, jnp.where(filled, got, 0), (at, 0)), dots

    return _tiles(plan.held, rows, tile, one_tile,
                  (_empty((rows, d), x.dtype), dots))


@functools.partial(jax.jit, static_argnames=("tile", "block"))
def _collect(y, plan: _Plan, weights=None, *, tile: int, block: int):
    """[rows, d] -> [n, d]: each token's held assignments summed
    (weighted by ``weights`` [n, k]), float32 accumulation. The rows are
    gathered in token order, ``block`` places at a time up to
    the last held one, and a token's neighbouring places are summed by
    one product with a [block, block] matrix that holds the weight of
    place p in the row of its token's first place; a block starts
    ``most - 1`` places before the previous one ends, so that every
    token's places lie whole in the block its first one is in. One
    gather of n rows then reads each token's sum."""
    n = plan.first_place.shape[0]
    rows, d = y.shape
    most = rows // n                       # of one token's assignments
    block = max(block, most)
    stride = block - most + 1
    at_once = max(1, tile // block)
    trips_at_most = -(-rows // (stride * at_once))
    exact = jax.lax.Precision.HIGHEST if y.dtype == jnp.float32 else None
    # a trip's places and the one before them, behind one another
    reach = at_once * stride + most
    room = (1, trips_at_most * at_once * stride + most - 1 - rows)
    tokens = jnp.pad(plan.token_of_place, room, constant_values=n)
    slots = jnp.pad(plan.slot_of_place, room)
    sources = jnp.pad(plan.row_of_place, room)

    def blocks(of, shift):
        return jnp.stack([of[b * stride + shift:b * stride + shift + block]
                          for b in range(at_once)])

    def some_blocks(i, sums):
        start = i * at_once * stride
        here = [jax.lax.dynamic_slice(of, (start,), (reach,))
                for of in (tokens, slots, sources)]
        token, before = blocks(here[0], 1), blocks(here[0], 0)
        ended = (start + jnp.arange(at_once)[:, None] * stride
                 + jnp.arange(block)) >= plan.held
        got = y[jnp.where(ended, 0, blocks(here[2], 1))]    # [b, block, d]
        weight = (1.0 if weights is None else weights.ravel()[
            jnp.where(ended, 0, blocks(here[1], 1))][:, None])
        # row q: the places of q's token, if q is its first (the places
        # past the last held one carry token n, which no token is)
        mix = jnp.where((token != before)[:, :, None]
                        & (token[:, :, None] == token[:, None]),
                        weight, 0).astype(y.dtype)      # [b, block, block]
        part = jnp.einsum("bqp,bpd->bqd", mix, got, precision=exact,
                          preferred_element_type=jnp.float32)
        return jax.lax.dynamic_update_slice(
            sums, part.astype(y.dtype).reshape(at_once * block, d),
            (i * at_once * block, 0))

    sums = jax.lax.fori_loop(
        0, _trips(_trips(plan.held, stride), at_once), some_blocks,
        _empty((trips_at_most * at_once * block, d), y.dtype))
    first = plan.first_place
    at = jnp.where(plan.has_place, first // stride * block + first % stride,
                   0)
    return jnp.where(plan.has_place[:, None], sums[at], 0)


# the tile and the block as they stand when the layer is traced
def _spread_now(x, plan, weights=None, y=None):
    return _spread(x, plan, weights, y, tile=BUFFER_TILE)


def _collect_now(y, plan, weights=None):
    return _collect(y, plan, weights, tile=BUFFER_TILE, block=SEGMENT_BLOCK)


@jax.custom_vjp
def _rows_out(x, plan):
    """[n, d] -> [rows, d]: the token of every sorted assignment
    (``_spread``). Its transpose is ``_collect``, so both directions
    are gathers (a scatter-add of thousands of rows serialises on the
    TPU), and both have a trip count read on the device, which has no
    reverse-mode rule: hence the ``custom_vjp``."""
    return _spread_now(x, plan)[0]


@jax.custom_vjp
def _rows_back(y, weights, plan):
    """[rows, d], [n, k] -> [n, d]: ``sum_s weights[t, s] * y[row of
    (t, s)]`` over a token's held assignments (``_collect``); no row
    past the last held assignment is read."""
    return _collect_now(y, plan, weights)


def _rows_back_bwd(res, g):
    y, weights, plan = res
    dy, dots = _spread_now(g, plan, weights, y)
    return dy, dots.reshape(weights.shape).astype(weights.dtype), None


_rows_out.defvjp(lambda x, plan: (_spread_now(x, plan)[0], plan),
                 lambda plan, g: (_collect_now(g, plan), None))
_rows_back.defvjp(
    lambda y, weights, plan: (_collect_now(y, plan, weights),
                              (y, weights, plan)),
    _rows_back_bwd)


def _swiglu_halves(ab):
    width = ab.shape[-1] // 2
    return nn.silu(ab[:, :width]) * ab[:, width:]


@functools.partial(jax.jit, static_argnames="tile")
def _gate(ab, held, g=None, *, tile: int):
    """[rows, 2 * width] -> [rows, width]: ``silu(a) * b`` of the two
    halves, over the tiles that hold assignments (the same bounded pass
    as ``_spread``); with ``g`` [rows, width], its gradient
    [rows, 2 * width] under that cotangent."""
    rows, both = ab.shape

    def one_tile(at, tile, filled, out):
        mine = jax.lax.dynamic_slice(ab, (at, 0), (tile, both))
        if g is None:
            new = _swiglu_halves(mine)
        else:
            new, = jax.vjp(_swiglu_halves, mine)[1](
                jax.lax.dynamic_slice(g, (at, 0), (tile, both // 2)))
        return jax.lax.dynamic_update_slice(
            out, jnp.where(filled, new, 0), (at, 0))

    return _tiles(held, rows, tile, one_tile, _empty(
        (rows, both // 2 if g is None else both), ab.dtype))


@jax.custom_vjp
def _gated(ab, held):
    """``_gate`` with its bounded backward pass."""
    return _gate(ab, held, tile=BUFFER_TILE)


_gated.defvjp(
    lambda ab, held: (_gate(ab, held, tile=BUFFER_TILE), (ab, held)),
    lambda res, g: (_gate(*res, g, tile=BUFFER_TILE), None))


class DroplessExperts(nn.Module):
    """Routed SwiGLU experts (sigmoid scores by default), the share held
    here: x [B, L, d] -> [B, L, d] (module docstring).

    Args:
      width: each routed expert's SwiGLU width.
      n_routed: the router's width -- every expert of the layer.
      n_held / first_held: this chip's experts are
        ``first_held .. first_held + n_held - 1``; what the others would
        add is left out (an expert-parallel caller sums the shares).
      top_k: experts per token, chosen by ``sigmoid score + bias``.
      route_scale: the weights are ``score / sum of the chosen scores``
        times this.
      shared_width: width of the shared expert every token passes
        through (0 = none).
      bias_step: after each training step ``bias += bias_step *
        sign(mean(count) - count)`` over the step's per-expert
        assignment counts; no gradient reaches ``bias`` (collection
        ``router_state``).
      router_init_std: the router matrix starts normal with this
        deviation (None: LeCun-normal, scores spread over ~0.2). Small
        (0.001: scores within ~0.01 of each other), ``bias_step``
        balances a fresh router's load within a few steps instead of
        hundreds.
      score: ``"sigmoid"`` (above) or ``"softmax"``: the scores are a
        float32 softmax over the ``n_routed`` experts and the top-k are
        chosen by them alone -- no bias steps, and ``bias`` stays at 0
        (it is declared all the same, for readers that rank experts by
        score + bias: the order is the logits' either way).
      shared_gate: the shared expert's output is scaled by
        ``sigmoid(x w)`` a token (``w`` [d, 1], param ``shared_gate``).
      aux_loss_coef: with the softmax score, sow ``coef * E * sum_e f_e
        P_e`` into ``losses`` (``moe_aux_loss``, which the Estimator adds
        to the loss): ``f_e`` the share of this apply's tokens that chose
        expert ``e`` (summing to ``top_k``), ``P_e`` its mean probability,
        over all ``E = n_routed`` experts (Switch Transformer's balance
        loss; only ``P`` carries a gradient). 0: nothing is sown.

    Collection ``counters`` holds cumulative int32 counts, updated on
    training applies and published by the Estimator at each epoch's
    host sync (docs/observability.md): ``moe_assignments``,
    ``moe_assignments_held``, ``moe_assignments_dropped`` (always 0:
    the buffer covers the worst case), ``moe_bias_steps``,
    ``moe_expert_assignments`` [n_held], and the tiles of the buffer the
    bounded passes visited and could have visited,
    ``moe_buffer_tiles_visited`` and ``moe_buffer_tiles``."""

    width: int
    n_routed: int
    n_held: int
    first_held: int = 0
    top_k: int = 8
    route_scale: float = 1.0
    shared_width: int = 0
    bias_step: float = 0.001
    router_init_std: Optional[float] = None
    score: str = "sigmoid"
    shared_gate: bool = False
    aux_loss_coef: float = 0.0
    dtype: Any = jnp.float32

    def _route(self, m, train: bool):
        """Weights [n, k] (float32), expert ids [n, k], counts [E]."""
        e = self.n_routed
        init = (nn.linear.default_kernel_init if self.router_init_std is None
                else nn.initializers.normal(self.router_init_std))
        logits = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                          kernel_init=init, name="router")(
            m.astype(jnp.float32))                           # [n, E]
        bias = self.variable("router_state", "bias",
                             lambda: jnp.zeros((e,), jnp.float32))
        if self.score == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
            _, idx = jax.lax.top_k(scores, self.top_k)
        elif self.score == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            _, idx = jax.lax.top_k(
                scores + jax.lax.stop_gradient(bias.value), self.top_k)
        else:
            raise ValueError(f"unknown router score {self.score!r}")
        # the chosen scores and the counts by comparison with every
        # expert's index: on the TPU a gather and a scatter-add of
        # n * k scalars cost three times this select and sum
        picked = idx[..., None] == jnp.arange(e)               # [n, k, E]
        chosen = jnp.sum(jnp.where(picked, scores[:, None], 0), axis=-1)
        counts = jnp.sum(picked, axis=(0, 1), dtype=jnp.int32)
        weights = chosen / (jnp.sum(chosen, -1, keepdims=True)
                            + 1e-20) * self.route_scale
        if self.aux_loss_coef:
            if self.score != "softmax":
                raise ValueError("the balance loss needs score='softmax'")
            share = counts.astype(jnp.float32) / m.shape[0]
            self.sow("losses", "moe_aux_loss", self.aux_loss_coef * e
                     * jnp.sum(share * jnp.mean(scores, axis=0)))
        if (self.score == "sigmoid" and train
                and self.is_mutable_collection("router_state")):
            load = counts.astype(jnp.float32)
            bias.value = bias.value + self.bias_step * jnp.sign(
                jnp.mean(load) - load)
        return weights, idx, counts

    def _count(self, counts, held, rows: int, train: bool):
        tile = min(BUFFER_TILE, rows)
        adds = {"moe_assignments": jnp.sum(counts),
                "moe_assignments_held": jnp.sum(held),
                "moe_assignments_dropped": jnp.zeros((), jnp.int32),
                "moe_bias_steps": jnp.ones((), jnp.int32),
                "moe_expert_assignments": held,
                "moe_buffer_tiles_visited": _trips(jnp.sum(held), tile),
                "moe_buffer_tiles": jnp.full((), -(-rows // tile),
                                             jnp.int32)}
        for name, add in adds.items():
            counter = self.variable(
                "counters", name,
                lambda a=add: jnp.zeros(a.shape, jnp.int32))
            if train and self.is_mutable_collection("counters"):
                counter.value = counter.value + add

    @nn.compact
    def __call__(self, x, train: bool = False):
        if not 0 <= self.first_held <= self.n_routed - self.n_held:
            raise ValueError(
                f"experts {self.first_held}..{self.first_held + self.n_held}"
                f" are not among the {self.n_routed} routed over")
        d, k, held_n = x.shape[-1], self.top_k, self.n_held
        m = x.reshape(-1, d).astype(self.dtype)
        n = m.shape[0]
        # a token's held assignments are at most min(k, held): a buffer
        # of that many rows a token covers the worst case, and nothing
        # is ever dropped; how much of it is touched is read on the device
        rows = n * min(k, held_n)
        with jax.named_scope("moe_route"):
            weights, idx, counts = self._route(m, train)
            # assignments per held expert: the grouped products' groups
            sizes = counts[self.first_held:self.first_held + held_n]
            self._count(counts, sizes, rows, train)

        def expert_param(name, shape):
            return self.param(name, nn.initializers.lecun_normal(
                in_axis=-2, out_axis=-1, batch_axis=(0,)),
                (held_n,) + shape).astype(self.dtype)

        w1 = expert_param("w1", (d, self.width))
        w3 = expert_param("w3", (d, self.width))
        w2 = expert_param("w2", (self.width, d))

        with jax.named_scope("moe_dispatch"):
            local = idx - self.first_held
            plan = _plan(jnp.where((local >= 0) & (local < held_n), local,
                                   held_n), sizes, rows=rows)
            xs = _rows_out(m, plan)
        with jax.named_scope("moe_experts"):
            # w1 | w3 side by side: one product, and one gradient for xs
            h = _gated(grouped_dot(xs, jnp.concatenate([w1, w3], axis=-1),
                                   sizes), plan.held)
            ys = grouped_dot(h, w2, sizes)
        with jax.named_scope("moe_combine"):
            out = _rows_back(ys, weights, plan)
        if self.shared_width:
            with jax.named_scope("moe_shared"):
                shared = SwiGLU(self.shared_width, dtype=self.dtype,
                                name="shared")(m)
                if self.shared_gate:
                    shared = shared * jax.nn.sigmoid(nn.Dense(
                        1, use_bias=False, dtype=self.dtype,
                        name="shared_gate")(m))
                out = out + shared
        return out.reshape(x.shape).astype(x.dtype)


class MoEFFN(nn.Module):
    """Top-k routed expert FFN band: x [B, L, H] -> [B, L, H].

    Args:
      hidden_size / intermediate_size: per-expert FFN dims.
      n_experts: expert count; must divide by the expert-axis size
        when expert parallelism engages.
      top_k: experts per token (1 = switch routing, 2 = classic MoE).
      expert_axis: mesh axis name to shard experts over ("auto" reads
        the ``zoo.mesh.axis.expert`` config key); engages when
        the context mesh carries that axis with size > 1 dividing
        ``n_experts``. None = always dense.
      layout: "broadcast" (exact, shards memory only) or "dispatch"
        (all_to_all token routing with ``capacity_factor``; shards
        compute too, overflow tokens drop). Dispatch requires the
        batch dim to divide by data_size * ep_size.
      capacity_factor: dispatch-layout expert capacity multiplier:
        each source device offers C = ceil(cf * n_local * top_k / E)
        slots per expert.
      aux_weight: multiplier folded into the sown load-balance loss.
    """

    hidden_size: int
    intermediate_size: int
    n_experts: int
    top_k: int = 2
    expert_axis: Optional[str] = None
    layout: str = "broadcast"
    capacity_factor: float = 1.25
    activation: str = "gelu"
    aux_weight: float = 0.01
    dtype: Any = jnp.float32

    def _act(self, h):
        return get_activation(self.activation)(h)

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.top_k < 1 or self.top_k > self.n_experts:
            raise ValueError(
                f"top_k must be in [1, {self.n_experts}], "
                f"got {self.top_k}")
        if self.layout not in ("broadcast", "dispatch"):
            raise ValueError("layout must be broadcast|dispatch, "
                             f"got {self.layout!r}")
        h = x.shape[-1]
        if h != self.hidden_size:
            raise ValueError(
                f"input feature dim {h} != hidden_size "
                f"{self.hidden_size}")
        e = self.n_experts
        # router stays fp32: tiny matmul, and gate ordering decides
        # discrete routing -- bf16 ties would flap expert assignment
        logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            x.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)          # [B, L, E]
        top_p, top_idx = jax.lax.top_k(probs, self.top_k)
        top_p = top_p / jnp.maximum(
            jnp.sum(top_p, -1, keepdims=True), 1e-9)
        # dense gate map [B, L, E]: renormalized weight where selected
        onehot = jax.nn.one_hot(top_idx, e, dtype=probs.dtype)
        gates = jnp.einsum("blk,blke->ble", top_p, onehot)

        # switch-transformer load-balance loss: E * sum_e f_e * p_e
        # (f = fraction of tokens routed to e, p = mean router prob)
        frac = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))  # [E]
        mean_p = jnp.mean(probs, axis=(0, 1))                  # [E]
        aux = self.aux_weight * e * jnp.sum(frac * mean_p)
        self.sow("losses", "moe_aux_loss", aux)

        # stacked expert params [E, ...] -- shardable over expert_axis
        wi = self.param("wi", nn.initializers.lecun_normal(),
                        (e, h, self.intermediate_size))
        bi = self.param("bi", nn.initializers.zeros,
                        (e, self.intermediate_size))
        wo = self.param("wo", nn.initializers.lecun_normal(),
                        (e, self.intermediate_size, h))
        bo = self.param("bo", nn.initializers.zeros, (e, h))

        xc = x.astype(self.dtype)
        gc = gates.astype(self.dtype)

        def experts_contrib(x_s, wi_s, bi_s, wo_s, bo_s, gates_s):
            """Sum of gated expert outputs for an expert slice; expert
            params cast to the compute dtype (params stay fp32)."""
            wi_c = wi_s.astype(self.dtype)
            wo_c = wo_s.astype(self.dtype)
            hmid = self._act(
                jnp.einsum("blh,ehm->eblm", x_s, wi_c)
                + bi_s.astype(self.dtype)[:, None, None])
            y = (jnp.einsum("eblm,emh->eblh", hmid, wo_c)
                 + bo_s.astype(self.dtype)[:, None, None])
            return jnp.einsum("ble,eblh->blh", gates_s, y)

        ep_size = 0
        mesh = None
        expert_axis = resolve_expert_axis(self.expert_axis)
        if expert_axis is not None:
            from analytics_zoo_tpu.parallel.mesh import (
                default_mesh, mesh_axis_size)

            mesh = default_mesh()
            if expert_axis in mesh.axis_names:
                ep_size = mesh_axis_size(mesh, expert_axis)
        if ep_size > 1 and e % ep_size == 0 \
                and self.layout == "dispatch" \
                and not self.is_initializing():
            # init traces with a 1-row example that cannot shard over
            # the token mesh; the dense path creates the IDENTICAL
            # parameter set, so init falls through below
            out = self._dispatch_ep(xc, wi, bi, wo, bo, top_idx, top_p,
                                    mesh, ep_size)
        elif ep_size > 1 and e % ep_size == 0:
            from jax.sharding import PartitionSpec as P

            axis = expert_axis
            # batch stays sharded over the data axis (dp x ep): each
            # device computes local_batch x local_experts, the psum
            # runs over the expert axis only
            data = ("data" if "data" in mesh.axis_names
                    and x.shape[0] % mesh_axis_size(mesh, "data") == 0
                    else None)

            def local(x_s, wi_s, bi_s, wo_s, bo_s, gates_s):
                out = experts_contrib(x_s, wi_s, bi_s, wo_s, bo_s,
                                      gates_s)
                # every device contributed only its resident experts;
                # the psum over the expert axis completes the routed sum
                return jax.lax.psum(out, axis)

            from analytics_zoo_tpu.parallel.mesh import shard_map

            espec = P(axis)
            out = shard_map(
                local, mesh,
                in_specs=(P(data, None, None), espec, espec, espec,
                          espec, P(data, None, axis)),
                out_specs=P(data, None, None))(
                xc, wi, bi, wo, bo, gc)
        else:
            out = experts_contrib(xc, wi, bi, wo, bo, gc)
        return out.astype(x.dtype)

    def _dispatch_ep(self, xc, wi, bi, wo, bo, top_idx, top_p, mesh,
                     ep_size):
        """GShard/Switch all-to-all dispatch: tokens shard over
        (data x expert) devices, experts shard over the expert axis,
        one all_to_all each way moves capacity buffers, not the full
        token stream. Slot-major priority queueing: across the local
        token shard, every first-choice assignment ranks ahead of any
        second choice; assignments past the per-expert capacity are
        dropped (contribute zero -- the caller's residual carries the
        token)."""
        import math

        from jax import lax
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu.parallel.mesh import mesh_axis_size

        axis = resolve_expert_axis(self.expert_axis)
        e, k = self.n_experts, self.top_k
        e_loc = e // ep_size
        data = ("data" if "data" in mesh.axis_names
                and mesh_axis_size(mesh, "data") > 1 else None)
        d_size = mesh_axis_size(mesh, "data") if data else 1
        shards = d_size * ep_size
        if xc.shape[0] % shards != 0:
            raise ValueError(
                f"dispatch MoE shards tokens over batch: batch "
                f"{xc.shape[0]} must divide by data*expert = {shards}")
        n_local = (xc.shape[0] // shards) * xc.shape[1]
        cap = max(1, math.ceil(self.capacity_factor * n_local * k / e))
        act, dtype = self._act, self.dtype

        def local(x_s, wi_s, bi_s, wo_s, bo_s, idx_s, w_s):
            b, L, h = x_s.shape
            n = b * L
            xf = x_s.reshape(n, h)
            sel = idx_s.reshape(n, k)
            w = w_s.reshape(n, k).astype(dtype)
            # slot-major priority: flatten (slot, token) so slot 0 of
            # every token enqueues before any slot 1 (Switch ordering)
            oh = jax.nn.one_hot(sel, e, dtype=jnp.int32)   # [n, k, E]
            ohf = oh.transpose(1, 0, 2).reshape(k * n, e)
            pos = jnp.cumsum(ohf, axis=0) - ohf            # queue pos
            keep = (pos < cap) & (ohf > 0)
            slot = jax.nn.one_hot(jnp.minimum(pos, cap - 1), cap,
                                  dtype=dtype)             # [k*n,E,C]
            disp_k = (keep[..., None] * slot).reshape(k, n, e, cap)
            dispatch = disp_k.sum(0)                       # [n, E, C]
            combine = jnp.einsum("knec,nk->nec", disp_k, w)

            # pack per-expert capacity buffers and ship each to the
            # expert's home device; tiled all_to_all over dim 0 is an
            # involution, so the same call routes results back
            buf = jnp.einsum("nec,nh->ech", dispatch, xf)  # [E, C, H]
            buf = lax.all_to_all(buf, axis, 0, 0, tiled=True)
            # received layout: dim 0 = (source peer, local expert)
            z = (buf.reshape(ep_size, e_loc, cap, h)
                 .transpose(1, 0, 2, 3).reshape(e_loc, ep_size * cap,
                                                h))
            hmid = act(jnp.einsum("egh,ehm->egm", z,
                                  wi_s.astype(dtype))
                       + bi_s.astype(dtype)[:, None])
            y = (jnp.einsum("egm,emh->egh", hmid, wo_s.astype(dtype))
                 + bo_s.astype(dtype)[:, None])
            y = (y.reshape(e_loc, ep_size, cap, h)
                 .transpose(1, 0, 2, 3).reshape(e, cap, h))
            y = lax.all_to_all(y, axis, 0, 0, tiled=True)
            out = jnp.einsum("nec,ech->nh", combine, y)
            return out.reshape(b, L, h)

        from analytics_zoo_tpu.parallel.mesh import shard_map

        tspec = P((data, axis) if data else axis, None, None)
        espec = P(axis)
        return shard_map(
            local, mesh,
            in_specs=(tspec, espec, espec, espec, espec,
                      P((data, axis) if data else axis, None, None),
                      P((data, axis) if data else axis, None, None)),
            out_specs=tspec)(
            xc, wi, bi, wo, bo, top_idx, top_p)


class MoE(KerasLayer):
    """Keras-layer wrapper for :class:`MoEFFN`."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 n_experts: int, top_k: int = 2,
                 expert_axis: Optional[str] = None,
                 layout: str = "broadcast",
                 capacity_factor: float = 1.25,
                 activation: str = "gelu", aux_weight: float = 0.01,
                 dtype: Any = jnp.float32, **kwargs):
        super().__init__(**kwargs)
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.n_experts = n_experts
        self.top_k = top_k
        self.expert_axis = expert_axis
        self.layout = layout
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.aux_weight = aux_weight
        self.dtype = dtype

    def _make_module(self):
        return MoEFFN(hidden_size=self.hidden_size,
                      intermediate_size=self.intermediate_size,
                      n_experts=self.n_experts, top_k=self.top_k,
                      expert_axis=self.expert_axis,
                      layout=self.layout,
                      capacity_factor=self.capacity_factor,
                      activation=self.activation,
                      aux_weight=self.aux_weight, dtype=self.dtype)


class MoETransformerBlock(nn.Module):
    """Post-LN transformer block whose FFN is a routed expert band --
    the standard MoE-transformer layer (attention unchanged, so it
    composes with the seq_axis ring/zigzag path like any block).

    Interleave with dense ``TransformerBlock``s for the usual
    every-other-layer MoE stack; the sown ``moe_aux_loss`` reaches the
    optimizer through the Estimator's ``aux_loss_collections``.
    """

    hidden_size: int
    n_head: int
    intermediate_size: int
    n_experts: int = 8
    top_k: int = 2
    expert_axis: Optional[str] = None
    layout: str = "broadcast"
    capacity_factor: float = 1.25
    activation: str = "gelu"
    aux_weight: float = 0.01
    hidden_dropout: float = 0.1
    attn_dropout: float = 0.1
    causal: bool = False
    ln_eps: float = 1e-5
    dtype: Any = jnp.float32
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, mask=None, key_padding_mask=None,
                 train: bool = False):
        from analytics_zoo_tpu.keras.layers.transformer import (
            MultiHeadSelfAttention)

        attn = MultiHeadSelfAttention(
            self.hidden_size, self.n_head,
            attn_dropout=self.attn_dropout, causal=self.causal,
            dtype=self.dtype, seq_axis=self.seq_axis,
            name="attention")(x, mask=mask,
                              key_padding_mask=key_padding_mask,
                              train=train)
        attn = Dropout(self.hidden_dropout, deterministic=not train)(attn)
        x = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype,
                         name="ln_attn")(x + attn)
        h = MoEFFN(hidden_size=self.hidden_size,
                   intermediate_size=self.intermediate_size,
                   n_experts=self.n_experts, top_k=self.top_k,
                   expert_axis=self.expert_axis, layout=self.layout,
                   capacity_factor=self.capacity_factor,
                   activation=self.activation,
                   aux_weight=self.aux_weight, dtype=self.dtype,
                   name="moe_ffn")(x, train=train)
        h = Dropout(self.hidden_dropout, deterministic=not train)(h)
        return nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype,
                            name="ln_ffn")(x + h)
