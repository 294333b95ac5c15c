"""Plain reference of the Zamba2 hybrid stack for the tests: the layer
equations of ``models/hybrid.py``'s docstring in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, one sequence at a time, with a
sequential state recurrence and full causal attention (no kernel, cache,
chunking or batching). It reads the program's parameter tree but none of its
code."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (T, H, D): interleaved pairs rotate by pos * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None] * inv
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(x.shape)


def _mamba(p, x, cfg):
    """Mamba2 mixer over one sequence x (T, d), from a zero state."""
    T = x.shape[0]
    d_in = cfg.mamba_expand * cfg.d_model
    P, N, G = cfg.mamba_headdim, cfg.ssm_state, cfg.mamba_ngroups
    nh = d_in // P
    proj = x @ p["in_proj"]
    z, xbc, dt = proj[:, :d_in], proj[:, d_in:-nh], proj[:, -nh:]
    K = p["conv_w"].shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(sum(xp[i:i + T] * p["conv_w"][i] for i in range(K))
                      + p["conv_b"])
    xs = xbc[:, :d_in].reshape(T, nh, P)
    Bm = xbc[:, d_in:d_in + G * N].reshape(T, G, N)
    Cm = xbc[:, d_in + G * N:].reshape(T, G, N)
    grp = np.arange(nh) // (nh // G)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    h = jnp.zeros((nh, P, N))
    ys = []
    for t in range(T):
        h = jnp.exp(A * dt[t])[:, None, None] * h + \
            (dt[t][:, None] * xs[t])[:, :, None] * Bm[t][grp][:, None, :]
        ys.append(jnp.einsum("hpn,hn->hp", h, Cm[t][grp]))
    y = jnp.stack(ys) + p["D"][None, :, None] * xs
    y = y.reshape(T, d_in) * jax.nn.silu(z)
    y = _rms(y.reshape(T, G, d_in // G), 1.0, cfg.norm_eps).reshape(T, d_in)
    return (y * p["gn_scale"]) @ p["out_proj"]


def _shared(sp, hp, u, cfg):
    T = u.shape[0]
    H, Dh = cfg.n_heads, cfg.d_head
    a = sp["attn"]
    un = _rms(u, sp["ln_in"]["scale"], cfg.norm_eps)
    pos = jnp.arange(T, dtype=jnp.float32)
    q = _rope((un @ a["wq"]).reshape(T, H, Dh), pos, cfg.rope_theta)
    k = _rope((un @ a["wk"]).reshape(T, H, Dh), pos, cfg.rope_theta)
    v = (un @ a["wv"]).reshape(T, H, Dh)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * (Dh / 2) ** -0.5
    causal = np.tril(np.ones((T, T), bool))
    w = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", w, v).reshape(T, H * Dh) @ a["wo"]
    y = _rms(o, sp["ln_ff"]["scale"], cfg.norm_eps)
    m = sp["mlp"]
    low = y @ hp["adapter_a"]
    g = y @ m["w_gate"] + low @ hp["adapter_gate"]
    up = y @ m["w_up"] + low @ hp["adapter_up"]
    f = jax.nn.gelu(g, approximate=False) * up
    return (f @ m["w_down"]) @ hp["linear"]


def logits(params, cfg, tokens) -> np.ndarray:
    """(T, V) float32 logits of one sequence ``tokens`` (T,)."""
    take = lambda tree, i: jax.tree.map(lambda t: t[i], tree)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda t: jnp.asarray(t, jnp.float32), params)
        x = p["embed"][jnp.asarray(tokens)]
        e = x
        inv = {l: k for k, l in enumerate(cfg.hybrid_layer_ids)}
        for l in range(cfg.n_layers):
            lp = take(p["mamba_layers"], l)
            t = 0.0
            if l in inv:
                k = inv[l]
                t = _shared(take(p["shared"], k % cfg.n_mem_blocks),
                            take(p["hybrid"], k),
                            jnp.concatenate([x, e], -1), cfg)
            x = x + _mamba(lp["mamba"], _rms(x + t, lp["ln"]["scale"],
                                             cfg.norm_eps), cfg)
        x = _rms(x, p["ln_f"]["scale"], cfg.norm_eps)
        head = p["embed"].T if "lm_head" not in p else p["lm_head"]
        return np.asarray(x @ head)
