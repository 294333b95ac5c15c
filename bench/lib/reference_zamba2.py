"""The plain reference for Zamba2 configurations, written from the layer
equations (``Zamba2ForCausalLM``; the program's reading of them is in its
``models/hybrid.py``) in straightforward ``jax.numpy``, one request at a
time. It imports nothing of the program.

With ``e`` the embedding output: a mamba layer is ``x + Mamba2(RMSNorm(x))``;
hybrid layer i (the k-th hybrid id, shared block k mod 2) is
``x + Mamba2(RMSNorm(x + t))`` with ``t = Linear_k(MLP_b,k(RMSNorm(Attn_b(
RMSNorm(concat(x, e))))))``; attention is MHA over the 2d-wide concat with
RoPE and scores scaled by ``(Dh / 2) ** -0.5``; the MLP is
``W_down(gelu(g) * u)`` with ``[g, u] = W_gu y + B_k A_k y``; Mamba2 is
in_proj -> [z | xBC | dt], a causal depthwise conv with bias and SiLU, the
state recurrence ``h_t = exp(A dt_t) h_{t-1} + dt_t x_t B_t^T``,
``y_t = h_t C_t + D x_t`` stepped token by token (head h reads B/C group
h // (heads / groups)), then ``y * silu(z)`` RMS-normalised per group and
out_proj; a final RMSNorm and the tied head.

Precision as ``bench/lib/reference.py``: float32 parameters, activations and
state at the backend's default matrix precision; the control is the same
reference in bfloat16 throughout. Departures shared with the program and
stated in the configuration file: interleaved RoPE pairs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.reference import CONTROL, PRECISIONS, _rmsnorm, _rope  # noqa: F401
from bench.lib.zamba2 import Sizes

NEG = -1e30


def _take(tree, i):
    return jax.tree.map(lambda t: t[i], tree)


def _mamba(s: Sizes, p, x, dtype):
    T = x.shape[0]
    cast = lambda a: a.astype(dtype)
    d_in, nh, P, N, G = s.d_in, s.nh, s.headdim, s.ssm_state, s.ngroups
    proj = x @ cast(p["in_proj"])
    z, xbc, dt = (proj[:, :d_in], proj[:, d_in:d_in + s.conv_dim],
                  proj[:, d_in + s.conv_dim:])
    K = s.conv
    xp = jnp.concatenate([jnp.zeros((K - 1, s.conv_dim), dtype), xbc])
    xbc = jax.nn.silu(sum(xp[i:i + T] * cast(p["conv_w"][i]) for i in range(K))
                      + cast(p["conv_b"]))
    xs = xbc[:, :d_in].reshape(T, nh, P)
    grp = np.arange(nh) // (nh // G)
    Bh = xbc[:, d_in:d_in + G * N].reshape(T, G, N)[:, grp]
    Ch = xbc[:, d_in + G * N:].reshape(T, G, N)[:, grp]
    dt = jax.nn.softplus(dt + cast(p["dt_bias"]))
    A = -jnp.exp(cast(p["A_log"]))

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(A * dt_t)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((nh, P, N), dtype), (xs, dt, Bh, Ch))
    y = (y + cast(p["D"])[None, :, None] * xs).reshape(T, d_in)
    y = (y * jax.nn.silu(z)).reshape(T, G, d_in // G)
    y = _rmsnorm(y, jnp.ones((), dtype), s.norm_eps).reshape(T, d_in)
    return (y * cast(p["gn_scale"])) @ cast(p["out_proj"])


def _shared(s: Sizes, sp, hp, u, pos, dtype):
    T = u.shape[0]
    cast = lambda a: a.astype(dtype)
    H, Dh = s.n_heads, s.d_head
    a = sp["attn"]
    un = _rmsnorm(u, sp["ln_in"]["scale"], s.norm_eps)
    q = _rope((un @ cast(a["wq"])).reshape(T, H, Dh), pos, s.rope_theta)
    k = _rope((un @ cast(a["wk"])).reshape(T, H, Dh), pos, s.rope_theta)
    v = (un @ cast(a["wv"])).reshape(T, H, Dh)
    causal = pos[:, None] >= pos[None, :]

    def head(qkv):   # one head at a time: a (T, T) score tile
        qh, kh, vh = qkv
        sc = (qh @ kh.T).astype(jnp.float32) * (Dh / 2) ** -0.5
        w = jax.nn.softmax(jnp.where(causal, sc, NEG), -1).astype(dtype)
        return w @ vh
    o = jax.lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                           v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2).reshape(T, H * Dh) @ cast(a["wo"])
    y = _rmsnorm(o, sp["ln_ff"]["scale"], s.norm_eps)
    m = sp["mlp"]
    low = y @ cast(hp["adapter_a"])
    g = y @ cast(m["w_gate"]) + low @ cast(hp["adapter_gate"])
    up = y @ cast(m["w_up"]) + low @ cast(hp["adapter_up"])
    f = (jax.nn.gelu(g, approximate=False) * up) @ cast(m["w_down"])
    return f @ cast(hp["linear"])


def forward(s: Sizes, p, tokens, dtype=jnp.float32):
    """Logits (T, V) for one causal sequence ``tokens`` (T,)."""
    x = p["embed"].astype(dtype)[tokens]
    e = x
    pos = jnp.arange(tokens.shape[0])
    call = {l: k for k, l in enumerate(s.hybrid_ids)}
    for l in range(s.n_layers):
        lp = _take(p["mamba_layers"], l)
        h = x
        if l in call:
            k = call[l]
            h = x + _shared(s, _take(p["shared"], k % s.n_mem_blocks),
                            _take(p["hybrid"], k),
                            jnp.concatenate([x, e], -1), pos, dtype)
        x = x + _mamba(s, lp["mamba"],
                       _rmsnorm(h, lp["ln"]["scale"], s.norm_eps), dtype)
    x = _rmsnorm(x, p["ln_f"]["scale"], s.norm_eps)
    head = p["embed"].T if s.tied else p["lm_head"]
    return (x @ head.astype(dtype)).astype(jnp.float32)


@functools.lru_cache(maxsize=8)
def _logits_fn(s: Sizes, length: int, precision: str):
    dtype = PRECISIONS[precision]
    return jax.jit(lambda p, tokens: forward(s, p, tokens, dtype))


def served_logits(s: Sizes, p, seq: np.ndarray, length: int,
                  precision: str = "float32") -> np.ndarray:
    """Logits (len(seq), V) of ``seq`` padded to ``length`` (one compiled
    shape for every request; the padding sits after the sequence, which the
    causal attention and the recurrence keep out of every position read)."""
    toks = np.zeros((length,), np.int32)
    toks[:len(seq)] = seq
    return np.asarray(_logits_fn(s, length, precision)(p, toks))[:len(seq)]
