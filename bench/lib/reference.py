"""The plain reference: a dense decoder (GQA attention with RoPE and optional
q/k/v bias, SwiGLU, RMSNorm, tied or untied head) in straightforward
``jax.numpy``, and the DP-SGD step the training cells run, written from the
published description. It imports nothing of the program.

It runs at the precision the configurations state: float32 parameters and
activations, matrix products at the backend's default precision for float32
(no ``jax.default_matmul_precision`` anywhere). The control is the same
reference one precision lower, ``bfloat16`` parameters and activations
throughout. Departures from the published model, all shared with the program
and stated in the configuration files: RoPE pairs interleaved elements (the
same model up to a fixed permutation of q/k columns under random weights),
and the RMSNorm eps is the one the configuration file states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.model import Sizes

NEG = -1e30


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """x (T, H, D); interleaved pairs (2i, 2i+1) rotate by pos * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, D/2)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1)
    return out.reshape(x.shape).astype(x.dtype)


def forward(s: Sizes, p, tokens, dtype=jnp.float32):
    """Logits (T, V) for one causal sequence ``tokens`` (T,), with
    parameters and activations in ``dtype``."""
    T = tokens.shape[0]
    cast = lambda a: a.astype(dtype)

    def mm(x, w):
        return x @ cast(w)
    x = cast(p["embed"])[tokens]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    lay = p["layers"]
    grp = s.n_heads // s.n_kv_heads
    for i in range(s.n_layers):
        a = jax.tree.map(lambda t: t[i], lay["attn"])
        h = _rmsnorm(x, lay["ln1"]["scale"][i], s.norm_eps)
        q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
        if s.qkv_bias:
            q, k, v = q + cast(a["bq"]), k + cast(a["bk"]), v + cast(a["bv"])
        q = _rope(q.reshape(T, s.n_heads, s.d_head), pos, s.rope_theta)
        k = _rope(k.reshape(T, s.n_kv_heads, s.d_head), pos, s.rope_theta)
        v = v.reshape(T, s.n_kv_heads, s.d_head)
        k, v = jnp.repeat(k, grp, axis=1), jnp.repeat(v, grp, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) \
            / np.sqrt(s.d_head)
        w = jax.nn.softmax(jnp.where(causal[None], sc, NEG), -1).astype(dtype)
        o = jnp.einsum("hqk,khd->qhd", w, v).reshape(T, -1)
        x = x + mm(o, a["wo"])
        f = jax.tree.map(lambda t: t[i], lay["ffn"])
        h = _rmsnorm(x, lay["ln2"]["scale"][i], s.norm_eps)
        x = x + mm(jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"]),
                   f["w_down"])
    x = _rmsnorm(x, p["ln_f"]["scale"], s.norm_eps)
    head = p["embed"].T if s.tied else p["lm_head"]
    return mm(x, head).astype(jnp.float32)


def loss(s: Sizes, p, rows, dtype=jnp.float32):
    """Mean next-token cross-entropy over ``rows`` (B, S+1)."""
    def one(r):
        lg = forward(s, p, r[:-1], dtype)
        lse = jax.nn.logsumexp(lg, -1)
        return jnp.mean(lse - jnp.take_along_axis(lg, r[1:, None], -1)[:, 0])
    return jnp.mean(jax.vmap(one)(rows))


# ---------------------------------------------------------------------------
# served tokens: logits over prompt + served tokens


PRECISIONS = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
CONTROL = "bfloat16"  # the control: one precision below the configurations'


@functools.lru_cache(maxsize=8)
def _logits_fn(s: Sizes, length: int, precision: str):
    dtype = PRECISIONS[precision]
    return jax.jit(lambda p, tokens: forward(s, p, tokens, dtype))


def served_logits(s: Sizes, p, seq: np.ndarray, length: int,
                  precision: str = "float32") -> np.ndarray:
    """Logits (len(seq), V) of ``seq`` padded to ``length`` (one compiled
    shape for every request; padding sits after the sequence, which causal
    attention keeps out of every position that is read). ``precision``:
    ``float32`` (the configurations') or ``bfloat16`` (the control)."""
    toks = np.zeros((length,), np.int32)
    toks[:len(seq)] = seq
    return np.asarray(_logits_fn(s, length, precision)(p, toks))[:len(seq)]


# ---------------------------------------------------------------------------
# DP noise: the 32-byte-key stream family of CITADEL++'s pairwise protocol.
# Threefry-2x32 (20 rounds) on counter (packed index, silo id), the top 23
# bits of each word as a uniform in (0, 1], Box-Muller with the stated
# degree-7 polynomial turn functions for cos(2 pi u) and sin(2 pi u).

_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))
_COS = (1.000000000e+00, -4.934802055e+00, 4.058712006e+00,
        -1.335262775e+00, 2.353304178e-01, -2.580626495e-02,
        1.928504556e-03, -1.035682435e-04)


def _threefry(k0, k1, x0, x1):
    u = jnp.uint32
    k2 = k0 ^ k1 ^ u(0x1BD11BDA)
    ks = (k0, k1, k2)
    x0, x1 = x0 + k0, x1 + k1
    for i in range(5):
        for r in _ROT[i % 2]:
            x0 = x0 + x1
            x1 = (x1 << u(r)) | (x1 >> u(32 - r))
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + u(i + 1)
    return x0, x1


def _unit(bits):
    f = jax.lax.bitcast_convert_type((bits >> jnp.uint32(9))
                                     | jnp.uint32(0x3F800000), jnp.float32)
    return 2.0 - f


def normal_stream(key2, idx, stream):
    """Standard normals for uint32 counters ``idx`` of silo ``stream``."""
    b0, b1 = _threefry(key2[0], key2[1], idx,
                       jnp.full(idx.shape, stream, jnp.uint32))
    u1, u2 = _unit(b0), _unit(b1)
    x = 2.0 * u2 - 1.0
    t = x * x
    acc = jnp.float32(_COS[-1])
    for c in _COS[-2::-1]:
        acc = acc * t + jnp.float32(c)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * (-acc)


def packed_offsets(tree) -> list:
    """Start of each leaf (``jax.tree`` order) in the protocol's packed
    buffer: leaves flattened, each padded to a multiple of 128."""
    offs, off = [], 0
    for leaf in jax.tree.leaves(tree):
        offs.append(off)
        off += -(-int(np.prod(leaf.shape)) // 128) * 128
    return offs


def step_keys(root_key, step: int):
    """(key_r, key_xi) raw uint32 pairs of protocol step ``step``."""
    k = jax.random.fold_in(root_key, step)
    kr, kx, _ = jax.random.split(k, 3)
    return (jax.random.key_data(kr).astype(jnp.uint32),
            jax.random.key_data(kx).astype(jnp.uint32))


def aggregate_noise(tree, key_t, key_prev, n_silos: int, sigma_c: float,
                    lam: float):
    """sigma*C*(xi_t - lam*xi_{t-1}) as a tree like ``tree``: each silo adds
    its share at sigma*C/sqrt(n) (``key_prev`` None at the first step)."""
    s = jnp.float32(sigma_c / np.sqrt(n_silos))
    out = []
    for leaf, off in zip(jax.tree.leaves(tree), packed_offsets(tree)):
        idx = jnp.arange(off, off + leaf.size, dtype=jnp.uint32)
        acc = jnp.zeros((leaf.size,), jnp.float32)
        for i in range(n_silos):
            share = s * normal_stream(key_t, idx, i)
            if key_prev is not None:
                share = share - jnp.float32(lam) * (
                    s * normal_stream(key_prev, idx, i))
            acc = acc + share
        out.append(acc.reshape(leaf.shape))
    return jax.tree.unflatten(jax.tree.structure(tree), out)


def leaf_norms(tree) -> dict:
    """Per-leaf L2 norms keyed by the leaf's path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(
        jnp.square(v.astype(jnp.float32)))) for k, v in flat}


def dp_steps(s: Sizes, p0, batches, root_key, *, n_silos: int, clip: float,
             sigma: float, lam: float, opt: dict, precision: str = "float32",
             noise: bool = True, rows_used: float = 1.0,
             program_first_grad=None):
    """``len(batches)`` DP-SGD steps from ``p0`` on ``batches`` (steps, B,
    S+1): rows split evenly over the silos, per-silo clip to ``clip``, sum,
    lambda-corrected noise, divide by the silo count, AdamW. Parameters,
    optimizer state and activations are kept in ``precision``.

    Returns host readings: ``losses`` per step, ``clip_mean_norms`` (per-leaf
    norms of the first step's clipped mean, noise excluded) and
    ``change_norms`` (per-leaf ||p_T - p_0||). ``rows_used`` < 1 keeps that
    share of each silo's rows (a planted fault). ``program_first_grad`` (a tree like ``p0``):
    the first gradient the program's optimizer got; its per-leaf norms with
    this reference's first-step noise taken out come back as
    ``program_clip_mean_norms``."""
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["eps"], opt["lr"]
    wd = opt.get("weight_decay", 0.0)
    dtype = PRECISIONS[precision]
    params = jax.tree.map(lambda a: a.astype(dtype), p0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    vg = jax.jit(jax.value_and_grad(lambda pp, r: loss(s, pp, r, dtype)))
    noise_fn = jax.jit(functools.partial(
        aggregate_noise, n_silos=n_silos, sigma_c=sigma * clip, lam=lam))
    out = {"losses": []}
    prev = None
    for t in range(batches.shape[0]):
        rows = batches[t].reshape(n_silos, -1, batches.shape[-1])
        keep = max(1, int(round(rows.shape[1] * rows_used)))
        total, losses = None, []
        for i in range(n_silos):
            li, g = vg(params, rows[i, :keep])
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                              for x in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))
            g = jax.tree.map(lambda x: x * scale, g)
            total = g if total is None else jax.tree.map(jnp.add, total, g)
            losses.append(float(li))
        out["losses"].append(float(np.mean(losses)))
        key_t = step_keys(root_key, t)[1]
        agg = total
        if noise:
            nz = noise_fn(total, key_t, prev)
            agg = jax.tree.map(jnp.add, total, nz)
        prev = key_t
        grad = jax.tree.map(lambda x: x / n_silos, agg)
        if t == 0:
            out["clip_mean_norms"] = {k: float(x) for k, x in leaf_norms(
                jax.tree.map(lambda x: x / n_silos, total)).items()}
            if program_first_grad is not None:
                nz1 = jax.tree.map(lambda a, b: (a - b) / n_silos,
                                   agg, total)
                out["program_clip_mean_norms"] = {
                    k: float(x) for k, x in leaf_norms(jax.tree.map(
                        lambda g, z: jnp.asarray(g, jnp.float32) - z,
                        program_first_grad, nz1)).items()}
        c = t + 1
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grad)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grad)
        bc1, bc2 = 1 - b1 ** c, 1 - b2 ** c
        params = jax.tree.map(
            lambda w, a, b: (w - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps)
                                       + wd * w)).astype(dtype), params, m, v)
    out["change_norms"] = {k: float(x) for k, x in leaf_norms(
        jax.tree.map(lambda a, b: a - b.astype(jnp.float32),
                     params, p0)).items()}
    return out
