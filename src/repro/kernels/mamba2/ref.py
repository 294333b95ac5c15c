"""Pure-jnp oracles for the Mamba-2 SSD chunked scan (scalar decay per head).

Mirrors models/mamba2.py's math: the kernel and the model share this oracle.
B and C come in ``G`` groups, (B, S, G, N), head h reading group
``h // (nh // G)``; a (B, S, N) array is one group shared by every head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def per_head(Bc, nh: int):
    """(B, S, G, N) or (B, S, N) group arrays -> (B, S, nh, N) per head."""
    if Bc.ndim == 3:
        Bc = Bc[:, :, None]
    return jnp.repeat(Bc, nh // Bc.shape[2], axis=2)


def ssd_sequential(xh, dt, la, Bc, Cc, h0):
    """xh: (B,S,nh,P); dt, la(=A*dt): (B,S,nh); Bc, Cc: (B,S,G,N) or
    (B,S,N); h0: (B,nh,P,N). Returns (y: (B,S,nh,P), h: (B,nh,P,N))."""
    nh = xh.shape[2]
    Bh, Ch = per_head(Bc, nh), per_head(Cc, nh)

    def step(h, t):
        a = jnp.exp(la[:, t])  # (B,nh)
        upd = jnp.einsum("bhp,bhn->bhpn", xh[:, t] * dt[:, t][..., None],
                         Bh[:, t])
        h1 = a[..., None, None] * h + upd
        y = jnp.einsum("bhpn,bhn->bhp", h1, Ch[:, t])
        return h1, y

    h, y = jax.lax.scan(step, h0, jnp.arange(xh.shape[1]))
    return y.transpose(1, 0, 2, 3), h


def ssd_chunked_jnp(xh, dt, la, Bc, Cc, h0, chunk: int = 64):
    """Chunked SSD (arXiv:2405.21060 block decomposition)."""
    B, S, nh, P = xh.shape
    N = Bc.shape[-1]
    Bc, Cc = per_head(Bc, nh), per_head(Cc, nh)
    C = min(chunk, S)
    if S % C != 0:
        return ssd_sequential(xh, dt, la, Bc, Cc, h0)
    nc = S // C

    def resh(t, feat):
        return t.reshape((B, nc, C) + feat).transpose(
            (1, 0, 2) + tuple(range(3, 3 + len(feat))))

    xc, dtc, lac = resh(xh, (nh, P)), resh(dt, (nh,)), resh(la, (nh,))
    Bcc, Ccc = resh(Bc, (nh, N)), resh(Cc, (nh, N))

    def chunk_step(h, inp):
        x_, dt_, la_, B_, C_ = inp
        L = jnp.cumsum(la_, axis=1)  # (B,C,nh)
        yin = jnp.einsum("bchn,bhpn,bch->bchp", C_, h, jnp.exp(L))
        ratio = L[:, :, None, :] - L[:, None, :, :]
        tri = jnp.tril(jnp.ones((C, C), bool))[None, :, :, None]
        G = jnp.exp(jnp.where(tri, ratio, -jnp.inf))
        scores = jnp.einsum("bthn,bshn,btsh->btsh", C_, B_, G)
        xdt = x_ * dt_[..., None]
        yintra = jnp.einsum("btsh,bshp->bthp", scores, xdt)
        Lend = L[:, -1:, :]
        w_s = jnp.exp(Lend - L)
        h1 = jnp.exp(Lend[:, 0, :, None, None]) * h + \
            jnp.einsum("bchp,bchn,bch->bhpn", xdt, B_, w_s)
        return h1, yin + yintra

    h, yc = jax.lax.scan(chunk_step, h0, (xc, dtc, lac, Bcc, Ccc))
    y = yc.transpose(1, 0, 2, 3, 4).reshape(B, S, nh, P)
    return y, h
