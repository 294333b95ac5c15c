"""Pallas TPU kernels for the packed flat-buffer DP hot path.

Two kernel families over buffers produced by ``core/flatbuf.py``:

* ``clip_sum_pallas``:  (B, P) -> ((P,), (B,))  one launch replacing the
  per-leaf sumsq + accumulate pair. Grid is (2, nd, nb): phase 0 streams the
  buffer accumulating per-example squared norms into a full-B VMEM scratch;
  phase 1 streams it again computing the DP-SGD clip factor
  min(1, C/||g_b||) on the fly and accumulating the clipped sum over
  examples. The clipped per-example tensor (O(B*P)) never exists in HBM.

* ``clip_mask_pallas``: (P,) -> (P,)  one launch fusing clip (externally
  computed scale), the pairwise zero-sum mask, the fresh DP noise xi_t and
  the lambda-corrected -lam*xi_{t-1} term. All four streams are regenerated
  from 32-byte keys *inside VMEM* (threefry2x32 counters = global packed
  indices), so masks and noise never touch HBM — one read + one write of the
  gradient for the whole barrier.

* ``noise_batch_pallas``: (P,) -> (P,)  ONE launch generating ALL n per-silo
  corrected-noise streams (xi_t share + lambda-corrected xi_{t-1} share,
  per-silo sigma_c/sqrt(k) scales and gates from SMEM vectors) and folding
  them onto the aggregate in silo order inside VMEM — replacing the n
  separate ``clip_mask_pallas(zeros, ...)`` launches of the engine's
  ``corrected_noise`` stage. The fold is the same sequential left fold, so
  the result is bit-identical to the sum-of-streams construction.

Scalars ride in SMEM. Counters are global element indices, so results are
independent of the blocking and bit-identical to the jnp oracles in
``ref.py`` for any block size.

Block geometry of ``noise_batch_pallas``. An f32 vreg holds (8, 128)
elements; a one-row ``(1, block_d)`` block fills one of its 8 sublanes, so
every threefry and Box-Muller op did 8x the work it needed, and a grid step
costs about 0.23 us on a v5e whatever it holds. The kernel therefore views
the ``(P,)`` buffer as ``(P // 128, 128)`` -- free, since a 1-D f32 array's
``T(1024)`` tiling is laid out byte for byte as ``(8, 128)`` tiles (the
``(P // 1024, 1024)`` view is not: it lowers to a relayout copy) -- and
streams it in ``(NOISE_BLOCK_ROWS, 128)`` blocks, a few hundred grid steps
at the packed size, with a ragged last block (``P // 128`` has no large
power-of-two factor; Pallas drops the out-of-range writes). Inside a block
a ``fori_loop`` walks ``NOISE_STRIP_ROWS``-row strips, so one strip's
threefry state stays in vregs instead of a whole block's temporaries
spilling to VMEM. A strip of 64 rows gives each op 8 independent vregs,
which hides the latency of threefry's serial add-rotate-xor chain: on a
v5e at the 2-silo packed size, 8-row strips took 61 ms and 64-row strips
25 ms, at any block of 256-4,096 rows. The counter of element (row, lane)
is still ``row * 128 + lane``, its global index.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.zsmask.threefry import normal_pair


LANES = 128             # a vreg's lane width
NOISE_BLOCK_ROWS = 2048  # rows of 128 elements per noise_batch grid step
NOISE_STRIP_ROWS = 64    # rows per strip of the noise_batch inner loop


def _block_b_for(B: int) -> int:
    for cand in (8, 4, 2, 1):
        if B % cand == 0:
            return cand
    return 1


# ---------------------------------------------------------------------------
# clip_sum: per-example sumsq + scale + accumulate, one launch


def _clip_sum_kernel(cb_ref, g_ref, sum_ref, norm_ref, ss_acc, d_acc, *,
                     nd: int, nb: int, block_b: int):
    p = pl.program_id(0)
    d = pl.program_id(1)
    b = pl.program_id(2)
    rows = (pl.dslice(b * block_b, block_b), slice(None))

    @pl.when(p == 0)
    def _phase_sumsq():
        g = g_ref[...].astype(jnp.float32)
        part = jnp.sum(g * g, axis=1, keepdims=True)  # (block_b, 1)

        @pl.when(d == 0)
        def _init():
            ss_acc[rows] = part

        @pl.when(d != 0)
        def _accum():
            ss_acc[rows] = ss_acc[rows] + part

    @pl.when(p == 1)
    def _phase_accumulate():
        g = g_ref[...].astype(jnp.float32)
        ss = ss_acc[rows]                              # (block_b, 1)
        norms = jnp.sqrt(jnp.maximum(ss, 1e-30))
        scale = jnp.minimum(1.0, cb_ref[0] / norms)
        part = jnp.sum(g * scale, axis=0, keepdims=True)  # (1, block_d)

        @pl.when(b == 0)
        def _init():
            d_acc[...] = part

        @pl.when(b != 0)
        def _accum():
            d_acc[...] += part

        @pl.when(b == nb - 1)
        def _flush_sum():
            sum_ref[...] = d_acc[...]

        @pl.when(d == nd - 1)
        def _flush_norms():
            norm_ref[...] = norms


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def clip_sum_pallas(g, clip_bound, block_d: int = 512, interpret: bool = False):
    """g: (B, P) packed per-example grads; P % block_d == 0 (flatbuf pads
    totals to ALIGN=1024). Returns (clipped_sum (P,), pre-clip norms (B,))."""
    B, P = g.shape
    block_d = min(block_d, P)
    assert P % block_d == 0, (P, block_d)
    block_b = _block_b_for(B)
    nb, nd = B // block_b, P // block_d
    cb = jnp.asarray(clip_bound, jnp.float32)[None]
    sum_out, norm_out = pl.pallas_call(
        functools.partial(_clip_sum_kernel, nd=nd, nb=nb, block_b=block_b),
        grid=(2, nd, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_b, block_d), lambda p, d, b: (b, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_d), lambda p, d, b: (0, d)),
            pl.BlockSpec((block_b, 1), lambda p, d, b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, P), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, 1), jnp.float32),
            pltpu.VMEM((1, block_d), jnp.float32),
        ],
        interpret=interpret,
    )(cb, g)
    return sum_out[0], norm_out[:, 0]


# ---------------------------------------------------------------------------
# clip_mask: clip + zero-sum mask + corrected noise, one launch


def _clip_mask_kernel(ints_ref, flts_ref, g_ref, o_ref, *, block_d: int,
                      use_pairwise: bool, use_prev: bool):
    di = pl.program_id(0)
    silo = ints_ref[0]
    nxt = ints_ref[1]     # pairwise ring neighbour (next *active* silo)
    key_r0, key_r1 = ints_ref[2].astype(jnp.uint32), ints_ref[3].astype(jnp.uint32)
    key_x0, key_x1 = ints_ref[4].astype(jnp.uint32), ints_ref[5].astype(jnp.uint32)
    key_p0, key_p1 = ints_ref[6].astype(jnp.uint32), ints_ref[7].astype(jnp.uint32)
    scale = flts_ref[0]
    s = flts_ref[1]       # per-stream noise std (sigma_c / sqrt(k))
    b_scale = flts_ref[2]
    lam_gate = flts_ref[3]
    s_prev = flts_ref[4]  # per-stream std of the step-(t-1) noise

    base = jnp.asarray(di * block_d).astype(jnp.uint32)
    idx = base + jax.lax.broadcasted_iota(jnp.uint32, (1, block_d), 1)

    def stream(k0, k1, sid):
        z0, _ = normal_pair(k0, k1, idx,
                            sid.astype(jnp.uint32) + jnp.zeros_like(idx))
        return z0

    out = g_ref[...].astype(jnp.float32) * scale
    if use_pairwise:
        out = out + b_scale * (stream(key_r0, key_r1, silo)
                               - stream(key_r0, key_r1, nxt))
    out = out + s * stream(key_x0, key_x1, silo)
    if use_prev:
        out = out - lam_gate * (s_prev * stream(key_p0, key_p1, silo))
    o_ref[...] = out


@functools.partial(jax.jit, static_argnames=(
    "n_silos", "use_pairwise", "use_prev", "block_d", "interpret"))
def clip_mask_pallas(g, scale, key_r, key_xi, prev_key, silo, n_silos: int,
                     sigma_c, b_scale, lam_gate, use_pairwise: bool = True,
                     use_prev: bool = True, block_d: int = 1024,
                     interpret: bool = False, *, nxt=None, noise_scale=None,
                     prev_noise_scale=None):
    """g: packed (P,) buffer; key_*: (2,) uint32; silo traceable int32.
    Returns fp32 ``g*scale + b*(r_i - r_nxt) + s*xi_t - lam_gate*s_prev*xi_prev``.
    ``nxt``/``noise_scale``/``prev_noise_scale`` default to the static-ring
    construction (see ref.clip_mask_ref); the elastic engine passes the
    active-set overrides through (all three may be traced scalars)."""
    P = g.shape[0]
    block_d = min(block_d, P)
    assert P % block_d == 0, (P, block_d)
    if nxt is None:
        nxt = (jnp.asarray(silo, jnp.int32) + 1) % n_silos
    if noise_scale is None:
        noise_scale = jnp.asarray(sigma_c, jnp.float32) / jnp.sqrt(float(n_silos))
    if prev_noise_scale is None:
        prev_noise_scale = noise_scale
    ints = jnp.stack([
        jnp.asarray(silo, jnp.int32), jnp.asarray(nxt, jnp.int32),
        key_r[0].astype(jnp.int32), key_r[1].astype(jnp.int32),
        key_xi[0].astype(jnp.int32), key_xi[1].astype(jnp.int32),
        prev_key[0].astype(jnp.int32), prev_key[1].astype(jnp.int32)])
    flts = jnp.stack([
        jnp.asarray(scale, jnp.float32),
        jnp.asarray(noise_scale, jnp.float32),
        jnp.asarray(b_scale, jnp.float32),
        jnp.asarray(lam_gate, jnp.float32),
        jnp.asarray(prev_noise_scale, jnp.float32)])

    out = pl.pallas_call(
        functools.partial(_clip_mask_kernel, block_d=block_d,
                          use_pairwise=use_pairwise, use_prev=use_prev),
        grid=(P // block_d,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_d), lambda d: (0, d)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda d: (0, d)),
        out_shape=jax.ShapeDtypeStruct((1, P), jnp.float32),
        interpret=interpret,
    )(ints, flts, g[None])
    return out[0]


# ---------------------------------------------------------------------------
# noise_batch: all n per-silo corrected-noise streams, one launch


def _noise_batch_kernel(ints_ref, flts_ref, scales_ref, lams_ref, g_ref,
                        o_ref, *, block_rows: int, strip_rows: int,
                        n_silos: int, use_prev: bool):
    bi = pl.program_id(0)
    key_x0 = ints_ref[0].astype(jnp.uint32)
    key_x1 = ints_ref[1].astype(jnp.uint32)
    key_p0 = ints_ref[2].astype(jnp.uint32)
    key_p1 = ints_ref[3].astype(jnp.uint32)
    s_prev = flts_ref[0]  # std of every silo's step-(t-1) share

    shape = (strip_rows, LANES)
    offset = (jax.lax.broadcasted_iota(jnp.uint32, shape, 0) * LANES
              + jax.lax.broadcasted_iota(jnp.uint32, shape, 1))
    block_base = bi.astype(jnp.uint32) * jnp.uint32(block_rows * LANES)

    def one_strip(j, carry):
        r0 = pl.multiple_of(j * strip_rows, strip_rows)
        # the counter is the element's global index in the (P,) buffer
        idx = block_base + r0.astype(jnp.uint32) * LANES + offset

        def stream(k0, k1, sid):
            z0, _ = normal_pair(k0, k1, idx,
                                sid.astype(jnp.uint32) + jnp.zeros_like(idx))
            return z0

        def add_share(i, out):
            # each share is built exactly as the per-silo clip_mask launch
            # did on a zeros buffer — (0 + s_i*xi_i) - lam_i*(s_prev*xp_i) —
            # then folded on in silo order: the left fold every tier
            # bit-matches
            share = 0.0 + scales_ref[i] * stream(key_x0, key_x1, i)
            if use_prev:
                share = share - lams_ref[i] * (s_prev
                                               * stream(key_p0, key_p1, i))
            return out + share

        rows = (pl.ds(r0, strip_rows), slice(None))
        out = g_ref[rows].astype(jnp.float32)
        o_ref[rows] = jax.lax.fori_loop(0, n_silos, add_share, out)
        return carry

    jax.lax.fori_loop(0, block_rows // strip_rows, one_strip, 0)


@functools.partial(jax.jit, static_argnames=("use_prev", "block_rows",
                                             "strip_rows", "interpret"))
def noise_batch_pallas(g_sum, key_xi, prev_key, noise_scales, lam_gates,
                       prev_noise_scale, use_prev: bool = True,
                       block_rows: int = NOISE_BLOCK_ROWS,
                       strip_rows: int = NOISE_STRIP_ROWS,
                       interpret: bool = False):
    """g_sum: packed (P,) aggregate; key_xi/prev_key: (2,) uint32;
    noise_scales/lam_gates: per-silo (n,) fp32 (participation gates folded
    in by the caller). Returns fp32
    ``g_sum + sum_i (s_i*xi_t^i - lam_i*s_prev*xi_{t-1}^i)`` with every
    stream regenerated inside VMEM — one launch for all n silos.

    The buffer is streamed as its free ``(P // 128, 128)`` view in
    ``(block_rows, 128)`` blocks (the last one ragged), each walked in
    ``strip_rows``-row strips (see the module docstring)."""
    P = g_sum.shape[0]
    n_silos = noise_scales.shape[0]
    assert P % LANES == 0, P
    rows = P // LANES
    block_rows = min(block_rows, rows)
    strip_rows = math.gcd(strip_rows, block_rows)
    ints = jnp.stack([
        key_xi[0].astype(jnp.int32), key_xi[1].astype(jnp.int32),
        prev_key[0].astype(jnp.int32), prev_key[1].astype(jnp.int32)])
    flts = jnp.asarray(prev_noise_scale, jnp.float32)[None]
    block = pl.BlockSpec((block_rows, LANES), lambda r: (r, 0))

    out = pl.pallas_call(
        functools.partial(_noise_batch_kernel, block_rows=block_rows,
                          strip_rows=strip_rows, n_silos=n_silos,
                          use_prev=use_prev),
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            block,
        ],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(ints, flts, noise_scales.astype(jnp.float32),
      lam_gates.astype(jnp.float32), g_sum.reshape(rows, LANES))
    return out.reshape(P)
