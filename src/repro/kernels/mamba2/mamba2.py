"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid: (B*nh, n_chunks) — chunk axis innermost; the (P, N) recurrent state
lives in VMEM scratch across the chunk steps of one (batch, head) column.

Per chunk (all MXU-friendly (C,N)/(C,P) tiles in VMEM):
  decay:  L = cumsum(la) within chunk (scalar per step for this head)
  inter:  y += (C ⊙ e^L) @ h                     (C,N) @ (N,P)
  intra:  scores = (C @ B^T) ⊙ Γ, Γ[t,s]=e^{L_t-L_s}·[s<=t]  (C,C)
          y += scores @ (dt ⊙ x)                  (C,C) @ (C,P)
  state:  h <- e^{L_C} h + ((dt⊙x) ⊙ e^{L_C-L})^T @ B   (P,C)@(C,N)

Scalar-per-head decay keeps Γ a 2-D (C,C) tile — the property Mamba-2 SSD
exploits for tensor-core execution (arXiv:2405.21060), mapped here to the MXU.

B and C come in G groups, head h reading group h // (nh // G): the B/C
block of grid row (b, h) is row b*G + h // (nh // G) of the (B*G, S, N)
group arrays, picked by the index map (no per-head copy of B or C).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dot_nt(a, b):
    """a (M, K) . b (N, K)^T -> (M, N), f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a (K, M)^T . b (K, N) -> (M, N), f32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, dt_ref, la_ref, b_ref, c_ref, h0_ref, y_ref, h_out_ref,
                state, *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state[...] = h0_ref[0]

    x = x_ref[0].astype(jnp.float32)      # (C, P)
    dt = dt_ref[0].astype(jnp.float32)    # (C, 1)
    la = la_ref[0].astype(jnp.float32)    # (C, 1)
    Bm = b_ref[0].astype(jnp.float32)     # (C, N)
    Cm = c_ref[0].astype(jnp.float32)     # (C, N)
    h = state[...]                        # (P, N)

    # L = cumsum(la) over the chunk: log2(C) shifted adds (no cumsum on TPU)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    L = la
    k = 1
    while k < chunk:
        L = L + jnp.where(row >= k, pltpu.roll(L, k, 0), 0.0)
        k *= 2
    # inter-chunk: y_t += (C_t e^{L_t}) . h^T
    y_inter = _dot_nt(Cm * jnp.exp(L), h)
    # intra-chunk: Gamma masked decay (2-D because decay is scalar per head)
    Lb = jnp.broadcast_to(L, (chunk, chunk))
    ratio = Lb - Lb.T                     # (C, C): L_t - L_s
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    G = jnp.exp(jnp.where(tri, ratio, NEG_INF))
    scores = _dot_nt(Cm, Bm) * G
    xdt = x * dt
    y_ref[0] = (y_inter + jnp.dot(scores, xdt,
                                  preferred_element_type=jnp.float32)
                ).astype(y_ref.dtype)
    # state update
    Lend = L[chunk - 1:chunk]             # (1, 1)
    w = jnp.exp(Lend - L)                 # (C, 1)
    decay = jnp.exp(jnp.broadcast_to(Lend, (1, h.shape[1])))  # lanes, then rows
    state[...] = decay * h + _dot_tn(xdt * w, Bm)

    @pl.when(ci == n_chunks - 1)
    def _done():
        h_out_ref[0] = state[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(xh, dt, la, Bc, Cc, h0, chunk: int = 64, interpret: bool = False):
    """xh: (B,S,nh,P) fp32; dt, la: (B,S,nh); Bc, Cc: (B,S,G,N), or
    (B,S,N) for one group; h0: (B,nh,P,N). Returns (y, h_final)."""
    B, S, nh, P = xh.shape
    if Bc.ndim == 3:
        Bc, Cc = Bc[:, :, None], Cc[:, :, None]
    G, N = Bc.shape[2], Bc.shape[3]
    hpg = nh // G
    chunk = min(chunk, S)
    assert S % chunk == 0 and nh % G == 0
    nc = S // chunk
    BH = B * nh

    xf = xh.transpose(0, 2, 1, 3).reshape(BH, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(BH, S, 1)
    laf = la.transpose(0, 2, 1).reshape(BH, S, 1)
    Bf = Bc.transpose(0, 2, 1, 3).reshape(B * G, S, N)
    Cf = Cc.transpose(0, 2, 1, 3).reshape(B * G, S, N)
    h0f = h0.reshape(BH, P, N)

    grid = (BH, nc)
    seq = lambda feat: pl.BlockSpec((1, chunk, feat), lambda bh, c: (bh, c, 0))
    grp = pl.BlockSpec((1, chunk, N),
                       lambda bh, c: ((bh // nh) * G + (bh % nh) // hpg, c, 0))
    st = pl.BlockSpec((1, P, N), lambda bh, c: (bh, 0, 0))

    y, hf = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc),
        grid=grid,
        in_specs=[seq(P), seq(1), seq(1), grp, grp, st],
        out_specs=[seq(P), st],
        out_shape=[jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
                   jax.ShapeDtypeStruct((BH, P, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xf, dtf, laf, Bf, Cf, h0f)

    y = y.reshape(B, nh, S, P).transpose(0, 2, 1, 3)
    return y, hf.reshape(B, nh, P, N)
