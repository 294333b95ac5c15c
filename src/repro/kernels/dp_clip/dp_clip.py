"""Pallas TPU kernels for fused per-example clip-and-accumulate (DP-SGD).

Two kernels over a (B, D) per-example-gradient block:

  1. ``sumsq``:      (B, D) -> (B,)  per-example partial squared norms,
                     accumulated across D-blocks in a VMEM scratch.
  2. ``clip_accum``: (B, D) x (B,) -> (D,)  clipped sum over examples,
                     accumulated across B-blocks.

Together with the tiny host-side combine of per-block sumsq into global
per-example norms, these avoid materializing the clipped per-example gradient
tensor (O(B*P)) in HBM — the paper's §4 clipping cost reduced to two streaming
passes. Block shapes are MXU/VPU aligned: lane dim multiples of 128, sublane
multiples of 8 (fp32).

``clip_accumulate`` also sums the packed ``(n_silos, P)`` stack of the
central DP tiers, where ``(min(8, B), 512)`` blocks made 453,022 grid steps
at the 2-silo packed size; at about 0.23 us a step on a v5e that pipeline
cost, not the bytes, set the kernel's time. A packed stack (B <= 8, D a
multiple of 1024) is therefore streamed in ``(B, PACKED_BLOCK_D)`` blocks,
a few thousand steps, the last one ragged (see :func:`accumulate_blocks`).
The kernel multiplies and adds once per element, so the sublanes that two
rows leave empty cost little; the operand stays ``(B, P)``, since a 3-D
view of it is not a bitcast.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
PACKED_BLOCK_D = 131072  # lanes per grid step over a packed (n_silos, P) stack


def _sumsq_kernel(g_ref, o_ref, acc, *, n_d: int):
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    g = g_ref[...].astype(jnp.float32)
    acc[...] += jnp.sum(g * g, axis=1, keepdims=True)

    @pl.when(di == n_d - 1)
    def _done():
        o_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("block_b", "block_d", "interpret"))
def per_example_sumsq(g, block_b: int = 8, block_d: int = 512, interpret: bool = False):
    B, D = g.shape
    block_b = min(block_b, B)
    block_d = min(block_d, D)
    assert B % block_b == 0 and D % block_d == 0
    nb, nd = B // block_b, D // block_d
    out = pl.pallas_call(
        functools.partial(_sumsq_kernel, n_d=nd),
        grid=(nb, nd),
        in_specs=[pl.BlockSpec((block_b, block_d), lambda b, d: (b, d))],
        out_specs=pl.BlockSpec((block_b, 1), lambda b, d: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, 1), jnp.float32)],
        interpret=interpret,
    )(g)
    return out[:, 0]


def _clip_accum_kernel(g_ref, s_ref, o_ref, *scratch, n_b: int):
    g = g_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)  # (block_b, 1)
    part = jnp.sum(g * s, axis=0, keepdims=True)
    if n_b == 1:  # every example in one block: nothing to carry
        o_ref[...] = part
        return
    bi = pl.program_id(1)
    acc, = scratch

    @pl.when(bi == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += part

    @pl.when(bi == n_b - 1)
    def _done():
        o_ref[...] = acc[...]


def accumulate_blocks(B: int, D: int) -> tuple[int, int]:
    """The ``(block_b, block_d)`` block :func:`clip_accumulate` streams a
    ``(B, D)`` operand in. A packed stack (B <= 8 rows, D a multiple of
    1024, ``flatbuf.ALIGN``) takes every row and ``PACKED_BLOCK_D`` lanes,
    so the grid has a few thousand steps; any other operand (a per-leaf
    block of B examples) keeps ``(min(8, B), min(512, D))``."""
    if B <= 8 and D % 1024 == 0:
        return B, min(PACKED_BLOCK_D, D)
    return min(8, B), min(512, D)


@functools.partial(jax.jit, static_argnames=("block_b", "block_d", "interpret"))
def clip_accumulate(g, scale, block_b: int | None = None,
                    block_d: int | None = None, interpret: bool = False):
    """sum_b g[b] * scale[b] -> (D,) fp32. Blocks default to
    :func:`accumulate_blocks`; a packed stack's last lane block may be
    ragged (its out-of-range lanes are never written back)."""
    B, D = g.shape
    auto_b, auto_d = accumulate_blocks(B, D)
    block_b = auto_b if block_b is None else min(block_b, B)
    block_d = auto_d if block_d is None else min(block_d, D)
    assert B % block_b == 0, (B, block_b)
    assert D % block_d == 0 or block_d % LANES == 0, (D, block_d)
    nb, nd = B // block_b, pl.cdiv(D, block_d)
    out = pl.pallas_call(
        functools.partial(_clip_accum_kernel, n_b=nb),
        grid=(nd, nb),
        in_specs=[
            pl.BlockSpec((block_b, block_d), lambda d, b: (b, d)),
            pl.BlockSpec((block_b, 1), lambda d, b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda d, b: (0, d)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        scratch_shapes=[] if nb == 1 else [pltpu.VMEM((1, block_d),
                                                       jnp.float32)],
        interpret=interpret,
    )(g, scale[:, None])
    return out[0]
