"""Pallas TPU kernel: fused zero-sum DP-mask generation + application.

The paper's admin generates masks and *ships O(P) tensors per silo per step*
(§4.2). Here the mask never exists in HBM at all: the kernel regenerates it
from a 32-byte key inside VMEM (threefry2x32 counter PRNG, add/xor/rot only)
and adds it to the gradient block in the same pass — one read + one write of
the gradient, zero mask traffic.

Grid: 1-D over D blocks. Scalars (silo id, n_silos, sigma_c/sqrt(n), B) ride
in SMEM. Counters are the global element indices so the mask is independent
of the block size (bit-identical to the jnp oracle for any blocking).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.zsmask.threefry import normal_pair


def _zsmask_kernel(ints_ref, flts_ref, g_ref, o_ref, *, block_d: int):
    di = pl.program_id(0)
    silo = ints_ref[0]
    n = ints_ref[1]
    key_r0, key_r1 = ints_ref[2].astype(jnp.uint32), ints_ref[3].astype(jnp.uint32)
    key_x0, key_x1 = ints_ref[4].astype(jnp.uint32), ints_ref[5].astype(jnp.uint32)
    sigma_scaled = flts_ref[0]  # sigma_c / sqrt(n)
    b_scale = flts_ref[1]

    base = jnp.asarray(di * block_d).astype(jnp.uint32)
    idx = base + jax.lax.broadcasted_iota(jnp.uint32, (1, block_d), 1)

    nxt = jnp.where(silo + 1 == n, 0, silo + 1)

    def stream(k0, k1, sid):
        z0, _ = normal_pair(k0, k1, idx, sid.astype(jnp.uint32) + jnp.zeros_like(idx))
        return z0

    r_i = stream(key_r0, key_r1, silo)
    r_next = stream(key_r0, key_r1, nxt)
    xi = stream(key_x0, key_x1, silo)
    mask = b_scale * (r_i - r_next) + sigma_scaled * xi
    o_ref[...] = g_ref[...].astype(jnp.float32) + mask


@functools.partial(jax.jit, static_argnames=("n_silos", "block_d", "interpret"))
def zsmask_pallas(g, key_r, key_xi, silo, n_silos: int, sigma_c, b_scale,
                  block_d: int = 1024, interpret: bool = False):
    """g: flat (D,). key_*: (2,) uint32. silo: int32 scalar (traceable)."""
    D = g.shape[0]
    block_d = min(block_d, D)
    assert D % block_d == 0
    ints = jnp.stack([
        jnp.asarray(silo, jnp.int32), jnp.asarray(n_silos, jnp.int32),
        key_r[0].astype(jnp.int32), key_r[1].astype(jnp.int32),
        key_xi[0].astype(jnp.int32), key_xi[1].astype(jnp.int32)])
    flts = jnp.stack([
        jnp.asarray(sigma_c, jnp.float32) / jnp.sqrt(float(n_silos)),
        jnp.asarray(b_scale, jnp.float32)])

    out = pl.pallas_call(
        functools.partial(_zsmask_kernel, block_d=block_d),
        grid=(D // block_d,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_d), lambda d: (0, d)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda d: (0, d)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
    )(ints, flts, g[None])
    return out[0]
