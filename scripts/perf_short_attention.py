"""On-chip timings behind ``ops.attention.FLASH_MIN_SEQ`` and the
short-row kernel's heads a grid step (docs/kernels.md "Measured
crossover", "Short rows"): self-attention without a mask at heads
12 x 64 in bfloat16, forward + backward, tokens held at 32 x 384, at
L 128 / 256 / 384 / 512 (and 1,024 for the two paths that serve it),
three ways:

- ``einsum``      ``attention._einsum_attention`` (scores in HBM),
- ``flash``       the blockwise owned kernel
                  (``pallas_attention.pallas_flash_attention_fwd``),
- ``short``       the short-row kernel
                  (``pallas_short_attention.pallas_short_attention``).

Every path starts from qkv ``[B, L, 3, 768]`` as the fused projection
writes it and ends in ``[B, L, 768]``, as ``MultiHeadSelfAttention``
calls it (so the first two pay their slices and heads-first
transposes); ``*_alone`` rows hand the first two their
``[B, H, L, 64]`` operands ready. All paths of a length run in one
process, interleaved round by round; a reading is the median round.
``--steps`` sweeps the short kernel's heads a step instead. Prints one
JSON line per reading.

    python scripts/perf_short_attention.py [--steps]
    JAX_PLATFORMS=cpu python scripts/perf_short_attention.py --rehearse
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops import pallas_short_attention as short_kernels
from analytics_zoo_tpu.ops.attention import _einsum_attention
from analytics_zoo_tpu.ops.pallas_attention import pallas_flash_attention_fwd
from analytics_zoo_tpu.ops.pallas_short_attention import (
    pallas_short_attention)

HEADS, HEAD_DIM, TOKENS = 12, 64, 32 * 384


def report(**kv):
    print(json.dumps(kv), flush=True)


def interleaved(fns: dict, args: dict, rounds: int, reps: int) -> dict:
    """Median over ``rounds`` of the milliseconds a call of each
    function takes, the functions taking turns inside every round."""
    for name, fn in fns.items():
        jax.block_until_ready(fn(*args[name]))
    took = {name: [] for name in fns}
    for _ in range(rounds):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(*args[name])
            jax.block_until_ready(out)
            took[name].append(1e3 * (time.perf_counter() - t0) / reps)
    return {name: statistics.median(ms) for name, ms in took.items()}


def heads_first(t):
    b, l, _ = t.shape
    return t.reshape(b, l, HEADS, HEAD_DIM).transpose(0, 2, 1, 3)


def rows(t):
    b, _, l, _ = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b, l, HEADS * HEAD_DIM)


def forward_backward(attend, n_operands: int):
    def loss(*args):
        *operands, ct = args
        return jnp.sum(attend(*operands).astype(jnp.float32) * ct)

    return jax.jit(jax.grad(loss, argnums=tuple(range(n_operands))))


def paths(l: int) -> dict:
    """name -> (attention on that path's own operands, layout)."""
    def from_fused(fn):
        return lambda qkv: rows(fn(*(
            heads_first(qkv[:, :, part]) for part in range(3))))

    def flash(q, k, v):
        return pallas_flash_attention_fwd(q, k, v, False)

    table = {
        "einsum": (from_fused(_einsum_attention), "fused"),
        "einsum_alone": (_einsum_attention, "heads"),
        "flash": (from_fused(flash), "fused"),
        "flash_alone": (flash, "heads"),
    }
    if l <= short_kernels.MAX_SEQ:
        table["short"] = (
            lambda qkv: pallas_short_attention(
                *(qkv[:, :, part] for part in range(3)), HEADS), "fused")
    return table


def operands(l: int, tokens: int) -> dict:
    b = tokens // l
    keys = jax.random.split(jax.random.PRNGKey(l), 2)
    qkv = jax.random.normal(keys[0], (b, l, 3, HEADS * HEAD_DIM),
                            jnp.bfloat16)
    ct = jax.random.normal(keys[1], (b, l, HEADS * HEAD_DIM), jnp.float32)
    return {"fused": (qkv, ct),
            "heads": tuple(heads_first(qkv[:, :, part])
                           for part in range(3)) + (heads_first(ct),)}


def crossover(lengths, tokens: int, rounds: int, reps: int):
    for l in lengths:
        given = operands(l, tokens)
        table = paths(l)
        args = {name: given[layout] for name, (_, layout) in table.items()}
        fns = {name: forward_backward(fn, len(args[name]) - 1)
               for name, (fn, _) in table.items()}
        # 2 forward + 5 backward products of 2 L^2 x 64 a head
        gflop = 7 * 2 * (tokens // l) * HEADS * l * l * HEAD_DIM / 1e9
        for name, ms in interleaved(fns, args, rounds, reps).items():
            report(what="attention_fwd_bwd", path=name, l=l,
                   batch=tokens // l, ms=ms, model_tflops=gflop / ms)


def steps(lengths, tokens: int, rounds: int, reps: int):
    """The short kernel alone by heads a grid step: ``_STEP_ROWS`` set
    to each count's rows in turn and the call compiled there and then
    (the rows are read while the kernels' jitted call is traced, so its
    cache is cleared before each; a compiled program outlives that)."""
    for l in lengths:
        if l > short_kernels.MAX_SEQ:
            continue
        given = operands(l, tokens)["fused"]
        fns = {}
        for heads in (2, 4, 6, 12):
            short_kernels._STEP_ROWS = heads * l
            jax.clear_caches()
            fns[heads] = forward_backward(paths(l)["short"][0], 1).lower(
                *given).compile()
        for heads, ms in interleaved(
                fns, {h: given for h in fns}, rounds, reps).items():
            report(what="short_fwd_bwd", heads_a_step=heads, l=l,
                   batch=tokens // l, ms=ms)


if __name__ == "__main__":
    rehearse = "--rehearse" in sys.argv
    if not rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: timings come from the chip (--rehearse checks "
                 "the script on the CPU at a tiny size)")
    report(what="device", platform=jax.devices()[0].platform,
           kind=jax.devices()[0].device_kind)
    lengths = (128, 256) if rehearse else (128, 256, 384, 512, 1024)
    size = dict(tokens=256, rounds=1, reps=1) if rehearse else dict(
        tokens=TOKENS, rounds=7, reps=20)
    (steps if "--steps" in sys.argv else crossover)(lengths, **size)
