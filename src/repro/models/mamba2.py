"""Mamba-2 (SSD) mixer — used by zamba2-7b.

Per head (headdim P, state N), scalar decay per head:
    h_t = a_t h_{t-1} + (dt_t x_t) outer B_t        h: (P, N)
    y_t = h_t C_t + D x_t
with a_t = exp(A * dt_t), A = -exp(A_log) < 0 per head, dt = softplus(dt_raw
+ dt_bias). B and C come in ``mamba_ngroups`` groups of N; head h reads group
h // (heads / groups). Chunked (SSD block decomposition, arXiv:2405.21060):
intra-chunk quadratic term with decay mask Gamma[t,s] = exp(la_t - la_s),
inter-chunk state scan (``kernels/mamba2``).

The mixer is split in three so that a serving step can scope each part:
``ssm_in`` (in_proj -> [z | xBC | dt], causal depthwise conv with bias and
SiLU), ``ssd`` (the chunked scan, or the one-step update for one token) and
``ssm_out`` (D skip, y * silu(z) RMS-normalised per group, out_proj).
``n_valid`` (B,) marks how many leading rows of each sequence are tokens: a
padded row gets dt = 0 (decay 1, no update) and is left out of the conv
tail, so the returned state is the state after exactly ``n_valid`` tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.mamba2 import ops as ssd_ops
from repro.kernels.mamba2.ref import per_head
from repro.models.layers import dense_init


def dims(cfg: ModelConfig) -> tuple[int, int, int, int, int, int]:
    """(d_in, heads, headdim, state, groups, conv channels)."""
    d_in = cfg.mamba_expand * cfg.d_model
    G, N = cfg.mamba_ngroups, cfg.ssm_state
    return (d_in, d_in // cfg.mamba_headdim, cfg.mamba_headdim, N, G,
            d_in + 2 * G * N)


def mamba2_init(key, cfg: ModelConfig, dtype=jnp.float32):
    d = cfg.d_model
    d_in, nh, _, _, _, conv_dim = dims(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "in_proj": dense_init(k1, d, d_in + conv_dim + nh, dtype),  # z,xBC,dt
        "conv_w": (jax.random.normal(k2, (cfg.mamba_conv, conv_dim), jnp.float32) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh).astype(jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "gn_scale": jnp.ones((d_in,), dtype),
        "out_proj": dense_init(k3, d_in, d, dtype),
    }


def _causal_conv(x, w, b, conv_state=None, n_valid=None):
    """Depthwise causal conv + SiLU. x: (B,S,C); w: (K,C); conv_state:
    (B,K-1,C), the inputs before x. Returns (out, the last K-1 inputs up to
    row ``n_valid`` of each sequence, or up to its end)."""
    K, S = w.shape[0], x.shape[1]
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + S] * w[i].astype(x.dtype) for i in range(K))
    if n_valid is None:
        tail = xp[:, S:]
    else:
        idx = n_valid[:, None] + jnp.arange(K - 1)[None, :]
        tail = jnp.take_along_axis(xp, idx[:, :, None], axis=1)
    return jax.nn.silu(out + b.astype(x.dtype)), tail


def ssm_in(p, x, cfg: ModelConfig, conv_state=None, n_valid=None):
    """in_proj and conv. Returns (z, (xh, dt, la, Bc, Cc) in f32, new conv
    tail); Bc/Cc are (B, S, G, N)."""
    B, S, _ = x.shape
    d_in, nh, P, N, G, conv_dim = dims(cfg)
    zxbcdt = x @ p["in_proj"].astype(x.dtype)
    z, xbc, dt_raw = jnp.split(zxbcdt, [d_in, d_in + conv_dim], axis=-1)
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state,
                                 n_valid)
    xs, Bc, Cc = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,nh)
    if n_valid is not None:
        dt = jnp.where(jnp.arange(S)[None, :, None] < n_valid[:, None, None],
                       dt, 0.0)
    la = -jnp.exp(p["A_log"]) * dt  # log decay per step
    f32 = lambda t, shape: t.reshape((B, S) + shape).astype(jnp.float32)
    scan_in = (f32(xs, (nh, P)), dt, la, f32(Bc, (G, N)), f32(Cc, (G, N)))
    return z, scan_in, new_conv


def ssd(scan_in, h0, cfg: ModelConfig, n_valid=None):
    """The SSD scan from state ``h0`` (B,nh,P,N) f32. Returns (y (B,S,nh,P),
    h). One token takes the one-step update; a sequence with
    ``n_valid == 0`` keeps ``h0``."""
    xh, dt, la, Bc, Cc = scan_in
    if xh.shape[1] == 1:
        nh = xh.shape[2]
        Bh, Ch = per_head(Bc, nh)[:, 0], per_head(Cc, nh)[:, 0]
        a = jnp.exp(la[:, 0])
        upd = jnp.einsum("bhp,bhn->bhpn", xh[:, 0] * dt[:, 0][..., None], Bh)
        h1 = a[..., None, None] * h0 + upd
        y = jnp.einsum("bhpn,bhn->bhp", h1, Ch)[:, None]
    else:
        y, h1 = ssd_ops.ssd_chunked(xh, dt, la, Bc, Cc, h0,
                                    chunk=cfg.mamba_chunk)
    if n_valid is not None:
        h1 = jnp.where((n_valid > 0)[:, None, None, None], h1, h0)
    return y, h1


def ssm_out(p, y, xh, z, cfg: ModelConfig):
    """D skip, y * silu(z) RMS-normalised per group of d_in / G channels
    (eps ``cfg.norm_eps``), out_proj."""
    B, S = z.shape[:2]
    d_in, _, _, _, G, _ = dims(cfg)
    y = y + p["D"][None, None, :, None] * xh
    y = y.reshape(B, S, d_in) * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(B, S, G, d_in // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + cfg.norm_eps)
    y = (yg.reshape(B, S, d_in) * p["gn_scale"].astype(jnp.float32)
         ).astype(z.dtype)
    return y @ p["out_proj"].astype(z.dtype)


def mamba2_apply(p, x, cfg: ModelConfig, state=None, n_valid=None):
    """x: (B,S,D). state: {'h': (B,nh,P,N), 'conv': (B,K-1,conv_dim)} or
    None. Returns (out, new_state)."""
    B = x.shape[0]
    _, nh, P, N, _, _ = dims(cfg)
    z, scan_in, new_conv = ssm_in(p, x, cfg, None if state is None
                                  else state["conv"], n_valid)
    h0 = jnp.zeros((B, nh, P, N), jnp.float32) if state is None else state["h"]
    y, h1 = ssd(scan_in, h0, cfg, n_valid)
    out = ssm_out(p, y, scan_in[0], z, cfg)
    if state is None:
        return out, None
    return out, {"h": h1, "conv": new_conv.astype(state["conv"].dtype)}
