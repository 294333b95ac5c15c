"""Pallas TPU flash attention (forward): online-softmax, causal + GQA.

Grid: (B, Hq, nQ, nK) with the KV axis innermost. Scratch in VMEM carries the
running max ``m``, denominator ``l`` and output accumulator across KV steps of
one query block; the output is written on the last KV step. Causal blocks
fully above the diagonal are masked via ``jnp.where`` (the index map still
visits them; the compiler's block-level predication elides fully-masked
compute on TPU — correctness first, see EXPERIMENTS.md §Perf).

Block sizes default to (128, 128): MXU-aligned (multiples of 128 on the
contracting and non-contracting dims), and the per-program VMEM working set
is q(128xD) + k,v(128xD) + acc(128xD) + scores(128x128) ~ 1 MB at D=128 fp32,
comfortably inside the ~16 MB VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, causal: bool, sm_scale: float, block_q: int,
                  block_k: int, n_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale  # (bq,bk)
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, causal: bool = True, block_q: int = 128,
                           block_k: int = 128, interpret: bool = False):
    """q: (B,Sq,Hq,D); k,v: (B,Sk,Hkv,D). Returns (B,Sq,Hq,D)."""
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    nq, nk = Sq // block_q, Sk // block_k

    qt = q.transpose(0, 2, 1, 3)  # (B,Hq,Sq,D)
    kt = k.transpose(0, 2, 1, 3)  # (B,Hkv,Sk,D)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, Hq, nq, nk)
    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // group, j, 0))
    o_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))

    out = pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, sm_scale=1.0 / D ** 0.5,
                          block_q=block_q, block_k=block_k, n_k=nk),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
