"""Pallas TPU kernel for the RWKV-6 chunked WKV scan.

Grid layout: (B*H, n_chunks) — the chunk axis is innermost so the recurrent
state lives in a VMEM scratch that persists across chunk steps of one (b, h)
program column; it is (re)initialized from the incoming state at chunk 0.

Per chunk (C x N blocks in VMEM):
  intra: scores[t,s] = sum_n r[t,n] k[s,n] exp(Lprev[t,n] - L[s,n]),  s < t
  bonus: diag term with u
  inter: o_t += (r_t * exp(Lprev_t)) @ S
  state: S <- exp(Ltot) * S + sum_s (k_s * exp(Ltot - L_s)) v_s^T

All decay ratios are <= 1 so the exponentials are numerically safe; compute is
fp32 throughout (MXU matmuls on (C,N)x(N,N) and (C,C)x(C,N) tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, s_out_ref,
                state, *, chunk: int, n_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state[...] = s0_ref[0]

    rr = r_ref[0].astype(jnp.float32)  # (C, N)
    kk = k_ref[0].astype(jnp.float32)
    vv = v_ref[0].astype(jnp.float32)
    ww = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (1, N) row

    lw = jnp.log(ww)
    L = jnp.cumsum(lw, axis=0)  # (C, N)
    Lprev = L - lw
    S0 = state[...]

    # inter-chunk contribution (MXU: (C,N) @ (N,N))
    o_inter = (rr * jnp.exp(Lprev)) @ S0

    # intra-chunk masked decay scores
    ratio = Lprev[:, None, :] - L[None, :, :]  # (t, s, N)
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_), k=-1)[:, :, None]
    dmat = jnp.where(tri, jnp.exp(ratio), 0.0)
    scores = jnp.einsum("tn,sn,tsn->ts", rr, kk, dmat,
                        preferred_element_type=jnp.float32)
    diag = jnp.sum(rr * kk * u, axis=1, keepdims=True)  # (C, 1)
    o_intra = scores @ vv + diag * vv

    o_ref[0] = (o_inter + o_intra).astype(o_ref.dtype)

    # state update
    Ltot = L[chunk - 1:chunk, :]  # (1, N)
    kd = kk * jnp.exp(Ltot - L)  # (C, N)
    state[...] = jnp.exp(Ltot[0])[:, None] * S0 + kd.T @ vv

    @pl.when(c == n_chunks - 1)
    def _final():
        s_out_ref[0] = state[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas(r, k, v, w, u, state0, chunk: int = 32, interpret: bool = False):
    """r,k,v,w: (B,S,H,N) fp32; u: (H,N); state0: (B,H,N,N) fp32."""
    B, S, H, N = r.shape
    assert S % chunk == 0
    nc = S // chunk
    BH = B * H

    def flat(t):  # (B,S,H,N) -> (B*H, S, N)
        return t.transpose(0, 2, 1, 3).reshape(BH, S, N)

    rf, kf, vf, wf = flat(r), flat(k), flat(v), flat(w)
    uf = jnp.broadcast_to(u[None, :, None, :], (B, H, 1, N)).reshape(BH, 1, N)
    s0 = state0.reshape(BH, N, N)

    grid = (BH, nc)
    blk_seq = pl.BlockSpec((1, chunk, N), lambda bh, c: (bh, c, 0))
    blk_u = pl.BlockSpec((1, 1, N), lambda bh, c: (bh, 0, 0))
    blk_state = pl.BlockSpec((1, N, N), lambda bh, c: (bh, 0, 0))

    o, s_out = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, n_chunks=nc),
        grid=grid,
        in_specs=[blk_seq, blk_seq, blk_seq, blk_seq, blk_u, blk_state],
        out_specs=[blk_seq, blk_state],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, N), jnp.float32),
            jax.ShapeDtypeStruct((BH, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0)

    o = o.reshape(B, H, S, N).transpose(0, 2, 1, 3)
    return o, s_out.reshape(B, H, N, N)
