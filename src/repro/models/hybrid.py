"""Zamba2 hybrid stack: a Mamba2 backbone with ``n_mem_blocks``
parameter-shared attention+MLP blocks, read from HF ``modeling_zamba2`` and
the published Zyphra/Zamba2-7B-Instruct ``config.json``.

With ``e`` the embedding output and RMSNorm eps ``cfg.norm_eps`` throughout:

* mamba layer:  ``x <- x + Mamba2(RMSNorm(x))``.
* hybrid layer i (the k-th id of ``hybrid_layer_ids``), served by shared
  block ``b = k mod n_mem_blocks``:
  ``t = Linear_k(Shared_b(concat(x, e), k))``;
  ``x <- x + Mamba2(RMSNorm(x + t))`` (the residual is taken before ``t`` is
  added).
* ``Shared_b(u, k)``: ``a = Attn_b(RMSNorm_2d(u))``, q, k and v 2d -> H x Dh
  (MHA, 32 x 224 published), RoPE theta ``rope_theta`` over the whole head,
  scores scaled by ``(Dh / 2) ** -0.5``, o H x Dh -> d; it returns
  ``MLP_b,k(RMSNorm_d(a))``, with no residual inside.
  ``MLP_b,k(y) = W_down(act(g) * u)``, ``[g, u] = W_gu y + B_k A_k y``, act
  the exact (erf) GELU, Zamba2's ``hidden_act``; ``A_k`` d -> r and ``B_k`` r -> 2 d_ff,
  one adapter pair per invocation (kept as ``adapter_a`` and the gate and
  up halves of ``B_k``).
* Mamba2 (``models/mamba2.py``): in_proj d -> [z 2d | xBC 2d + 2GN | dt
  nh], causal depthwise conv of 4 with bias and SiLU, B and C of G groups
  (head h reads group h // (nh / G)), ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``, the SSD recurrence plus ``D x``, then ``y * silu(z)``
  RMS-normalised per group of 2d / G channels, out_proj.
* head: a final RMSNorm, then the head, tied to the embedding when
  ``tie_embeddings`` (Zamba2Config's default; the published file lacks the
  key).

Departures from HF: RoPE rotates interleaved pairs (x[2i], x[2i+1]) where
HF's ``rotate_half`` pairs (x[i], x[i + Dh/2]) — the same model up to a fixed
permutation of each head's q and k columns; the scale is applied as q x
sqrt(2) before the kernels' 1/sqrt(Dh); HF's torch fallback clamps dt below
at ``time_step_min`` where its fused-kernel path (``time_step_limit`` null)
does not, and this follows the kernel path; the gated norm multiplies its
weight in float32 before the cast (the same in float32).

Parameters: ``mamba_layers`` stacked over all layers (a hybrid layer's own
mixer included), ``shared`` stacked over the blocks, ``hybrid`` (adapters
and output projection) stacked over the invocations. Consecutive mamba
layers run as one ``lax.scan``; hybrid layers are unrolled, since the block
and the invocation they use are static.

Serving (``init_paged_cache`` / ``forward_paged``): each shared-block
invocation has its own KV pages in the head-major (N, Hkv, P, Dp) layout,
stacked (K, N, ...), each head zero-padded from Dh to Dp, whole 128-lane
vregs (224 -> 256 published): a (P, 224) tile is not one the TPU lays out
unpadded, and XLA would otherwise keep the pool in a layout of its own and
copy all of it into the kernel's around every call; each mamba layer keeps one recurrent state slot per
sequence, ``h`` (L, slots, nh, P, N) f32 and ``conv`` (L, slots, K-1, conv
channels), layer-major so the layer scan slices it without a transpose. The
slot of a sequence is its row of ``tokens``. ``reset_slot_state`` zeroes a
slot's state on the device; the page pool calls it on admission, beside the
zeroing of the slot's fresh pages.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding_rules import constrain
from repro.models import attention as attn
from repro.models import mamba2
from repro.models.layers import (
    chunked_lm_loss,
    dense_init,
    embed_init,
    rmsnorm,
    rmsnorm_init,
)

SQRT2 = 2.0 ** 0.5


def plan(cfg: ModelConfig) -> list[tuple[str, int, int]]:
    """The layer stack in order: ``("mamba", start, stop)`` runs of mamba
    layers and ``("hybrid", layer, invocation)`` entries."""
    inv = {l: k for k, l in enumerate(cfg.hybrid_layer_ids)}
    out, start = [], 0
    for l in range(cfg.n_layers):
        if l in inv:
            if start < l:
                out.append(("mamba", start, l))
            out.append(("hybrid", l, inv[l]))
            start = l + 1
    if start < cfg.n_layers:
        out.append(("mamba", start, cfg.n_layers))
    return out


def mamba_layer_init(key, cfg: ModelConfig, dtype=jnp.float32):
    return {"ln": rmsnorm_init(cfg.d_model, dtype),
            "mamba": mamba2.mamba2_init(key, cfg, dtype)}


def shared_init(key, cfg: ModelConfig, dtype=jnp.float32):
    d, a = cfg.d_model, 2 * cfg.d_model
    kq, kk, kv, ko, kg, ku, kd = jax.random.split(key, 7)
    return {
        "ln_in": rmsnorm_init(a, dtype),
        "attn": {"wq": dense_init(kq, a, cfg.attn_inner, dtype),
                 "wk": dense_init(kk, a, cfg.kv_inner, dtype),
                 "wv": dense_init(kv, a, cfg.kv_inner, dtype),
                 "wo": dense_init(ko, cfg.attn_inner, d, dtype)},
        "ln_ff": rmsnorm_init(d, dtype),
        "mlp": {"w_gate": dense_init(kg, d, cfg.d_ff, dtype),
                "w_up": dense_init(ku, d, cfg.d_ff, dtype),
                "w_down": dense_init(kd, cfg.d_ff, d, dtype)},
    }


def invocation_init(key, cfg: ModelConfig, dtype=jnp.float32):
    d, r = cfg.d_model, cfg.adapter_rank
    ka, kg, ku, kl = jax.random.split(key, 4)
    return {"adapter_a": dense_init(ka, d, r, dtype),
            "adapter_gate": dense_init(kg, r, cfg.d_ff, dtype),
            "adapter_up": dense_init(ku, r, cfg.d_ff, dtype),
            "linear": dense_init(kl, d, d, dtype)}


def init(key, cfg: ModelConfig, dtype=jnp.float32):
    ke, km, ks, kh, kl = jax.random.split(key, 5)
    stack = lambda fn, k, n: jax.vmap(lambda kk: fn(kk, cfg, dtype))(
        jax.random.split(k, n))
    params = {
        "embed": embed_init(ke, cfg.vocab_size, cfg.d_model, dtype),
        "mamba_layers": stack(mamba_layer_init, km, cfg.n_layers),
        "shared": stack(shared_init, ks, cfg.n_mem_blocks),
        "hybrid": stack(invocation_init, kh, len(cfg.hybrid_layer_ids)),
        "ln_f": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kl, cfg.d_model, cfg.vocab_size, dtype)
    return params


def _take(tree, i):
    return jax.tree.map(lambda t: t[i], tree)


def _head(params):
    head = params.get("lm_head")
    return params["embed"].T if head is None else head


# ---------------------------------------------------------------------------
# layer pieces, shared by the training forward and the serving step


def mamba_layer_apply(p, x, cfg, t=None, state=None, n_valid=None):
    """``x + Mamba2(RMSNorm(x + t))``; ``t`` the hybrid layer's shared-block
    output (None on a mamba layer)."""
    y = rmsnorm(p["ln"], x if t is None else x + t, cfg.norm_eps)
    h, ns = mamba2.mamba2_apply(p["mamba"], y, cfg, state, n_valid)
    return x + h, ns


def _shared_qkv(sp, x, e, cfg, positions):
    u = jnp.concatenate([x, e], axis=-1)
    u = rmsnorm(sp["ln_in"], u, cfg.norm_eps)
    q, k, v = attn._project_qkv(sp["attn"], u, cfg, positions)
    return (q * SQRT2).astype(q.dtype), k, v


def _shared_mlp(sp, hp, a, cfg):
    """``Linear_k(MLP_b,k(RMSNorm(a)))`` for the attention output ``a``."""
    y = rmsnorm(sp["ln_ff"], a, cfg.norm_eps)
    m = sp["mlp"]
    low = y @ hp["adapter_a"].astype(y.dtype)
    g = y @ m["w_gate"].astype(y.dtype) + low @ hp["adapter_gate"].astype(y.dtype)
    u = y @ m["w_up"].astype(y.dtype) + low @ hp["adapter_up"].astype(y.dtype)
    f = constrain(jax.nn.gelu(g, approximate=False) * u, "batch", None, "ff")
    f = f @ m["w_down"].astype(y.dtype)
    return f @ hp["linear"].astype(y.dtype)


def _attend(q, k, v, cache=None, use_flash=False):
    """Causal attention of q over k/v, after the cache's tokens when given.
    Returns ((B, S, H, Dh), new cache)."""
    if cache is None:
        if use_flash:
            from repro.kernels.flash_attention import ops as flash_ops
            return flash_ops.flash_attention(q, k, v, causal=True), None
        return attn._sdpa(q, k, v, True, q_offset=0), None
    idx = cache["len"]
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
    valid = jnp.arange(ck.shape[1]) < idx + q.shape[1]
    out = attn._sdpa_masked(q, ck, cv, True, idx, valid)
    return out, {"k": ck, "v": cv, "len": idx + q.shape[1]}


def shared_apply(sp, hp, x, e, cfg, positions, cache=None, use_flash=False):
    B, S, _ = x.shape
    q, k, v = _shared_qkv(sp, x, e, cfg, positions)
    o, nc = _attend(q, k, v, cache, use_flash)
    a = o.astype(x.dtype).reshape(B, S, cfg.attn_inner) \
        @ sp["attn"]["wo"].astype(x.dtype)
    return _shared_mlp(sp, hp, a, cfg), nc


# ---------------------------------------------------------------------------
# training / contiguous-cache forward


def forward(params, cfg: ModelConfig, batch: dict, state=None, remat=False,
            compute_dtype=jnp.bfloat16, logits_mode="all", use_flash=False):
    x = params["embed"].astype(compute_dtype)[batch["tokens"]]
    x = constrain(x, "batch", None, None)
    e = x
    B, S, _ = x.shape
    offset = 0 if state is None else state["attn_cache"]["len"][0]
    positions = jnp.broadcast_to(offset + jnp.arange(S)[None], (B, S))
    layers, nb = params["mamba_layers"], cfg.n_mem_blocks
    hs, convs, caches = [], [], []

    def mamba_run(x, a, b):
        lp = jax.tree.map(lambda t: t[a:b], layers)
        if state is None:
            def body(h, p):
                return mamba_layer_apply(p, h, cfg)[0], None
            if remat:
                body = jax.checkpoint(body, prevent_cse=False)
            return jax.lax.scan(body, x, lp)[0]

        def body_s(h, inp):
            p, sh, sc = inp
            h, ns = mamba_layer_apply(p, h, cfg, None, {"h": sh, "conv": sc})
            return h, (ns["h"], ns["conv"])
        x, (nh_, nc_) = jax.lax.scan(
            body_s, x, (lp, state["h"][a:b], state["conv"][a:b]))
        hs.append(nh_)
        convs.append(nc_)
        return x

    def hybrid_layer(x, l, k, cache):
        t, nc = shared_apply(_take(params["shared"], k % nb),
                             _take(params["hybrid"], k), x, e, cfg, positions,
                             cache, use_flash)
        ms = None if state is None else {"h": state["h"][l],
                                         "conv": state["conv"][l]}
        x, ns = mamba_layer_apply(_take(layers, l), x, cfg, t, ms)
        return constrain(x, "batch", None, None), ns, nc

    for kind, a, b in plan(cfg):
        if kind == "mamba":
            x = mamba_run(x, a, b)
        elif state is None:
            fn = functools.partial(hybrid_layer, l=a, k=b, cache=None)
            if remat:
                fn = jax.checkpoint(fn, prevent_cse=False)
            x = fn(x)[0]
        else:
            x, ns, nc = hybrid_layer(x, a, b, _take(state["attn_cache"], b))
            hs.append(ns["h"][None])
            convs.append(ns["conv"][None])
            caches.append(nc)

    new_state = None
    if state is not None:
        new_state = {"h": jnp.concatenate(hs, 0),
                     "conv": jnp.concatenate(convs, 0),
                     "attn_cache": jax.tree.map(lambda *t: jnp.stack(t),
                                                *caches)}
    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    if logits_mode == "hidden":
        return x, new_state
    if logits_mode == "last":
        x = x[:, -1:]
    logits = x @ _head(params).astype(x.dtype)
    logits = constrain(logits, "batch", None, "vocab")
    return logits, new_state


def init_state(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    _, nh, P, N, _, conv_dim = mamba2.dims(cfg)
    return {
        "h": jnp.zeros((cfg.n_layers, batch, nh, P, N), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.mamba_conv - 1, conv_dim), dtype),
        "attn_cache": attn.init_kv_cache(cfg, batch, max_len,
                                         len(cfg.hybrid_layer_ids), dtype),
    }


def loss_fn(params, cfg, batch, remat=False, compute_dtype=jnp.bfloat16, use_flash=False):
    hidden, _ = forward(params, cfg, batch, None, remat, compute_dtype,
                        logits_mode="hidden", use_flash=use_flash)
    return chunked_lm_loss(hidden, _head(params), batch["labels"])


def decode_step(params, cfg, batch, state, compute_dtype=jnp.bfloat16):
    logits, state = forward(params, cfg, batch, state,
                            compute_dtype=compute_dtype, logits_mode="last")
    return logits[:, 0], state


# ---------------------------------------------------------------------------
# paged serving: KV pages for the shared-block invocations, a recurrent
# state slot per sequence for each mamba layer


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     n_slots: int, dtype=jnp.float32) -> dict:
    _, nh, P, N, _, conv_dim = mamba2.dims(cfg)
    L = cfg.n_layers
    pool = attn.init_paged_pool(cfg, n_pages, page_size,
                                len(cfg.hybrid_layer_ids), dtype)
    pool["h"] = jnp.zeros((L, n_slots, nh, P, N), jnp.float32)
    pool["conv"] = jnp.zeros((L, n_slots, cfg.mamba_conv - 1, conv_dim), dtype)
    return pool


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _zero_slot(h, conv, slot):
    return h.at[:, slot].set(0), conv.at[:, slot].set(0)


def reset_slot_state(pool: dict, slot) -> dict:
    """Zero slot ``slot``'s recurrent state in every mamba layer, on the
    device and in place (the state arrays are donated: rebind the pool)."""
    h, conv = _zero_slot(pool["h"], pool["conv"], jnp.int32(slot))
    return {**pool, "h": h, "conv": conv}


def _mamba_paged(p, x, cfg, t, h, conv, n_valid):
    """One mamba layer of a serving step, scoped for a profile."""
    with jax.named_scope("paged.ssm_in"):
        y = rmsnorm(p["ln"], x if t is None else x + t, cfg.norm_eps)
        z, scan_in, conv = mamba2.ssm_in(p["mamba"], y, cfg, conv, n_valid)
    with jax.named_scope("paged.ssd"):
        out, h = mamba2.ssd(scan_in, h, cfg, n_valid)
    with jax.named_scope("paged.ssm_out"):
        x = x + mamba2.ssm_out(p["mamba"], out, scan_in[0], z, cfg)
    return x, h, conv


def forward_paged(params, cfg: ModelConfig, tokens, pool: dict, tables,
                  q_start, n_valid, compute_dtype=jnp.bfloat16,
                  logits_mode="last"):
    """One serving step, as ``transformer.forward_paged``: C new tokens per
    slot (a prefill chunk, or decode at C == 1), the slot being the row.
    Each mamba layer runs from the slot's stored state; rows past
    ``n_valid`` leave it as it was (an idle slot's state is untouched).

    The invocations' stacked (K, N, ...) pages are viewed as one (K*N, ...)
    pool, invocation k's pages offset by k*N in its table: a reshape of the
    leading axes, so no pool is sliced or copied.

    Scopes: ``paged.ssm_in``, ``paged.ssd``, ``paged.ssm_out`` in each
    mamba layer; ``paged.shared`` (concat, norms, adapters, MLP, output
    projection), ``paged.qkv``, ``paged.kv_write``, ``paged.attention`` in
    each shared-block invocation; ``paged.head``."""
    with jax.named_scope("paged.head"):
        x = params["embed"].astype(compute_dtype)[tokens]
    e = x
    B, S = tokens.shape
    positions = q_start[:, None] + jnp.arange(S)[None, :]
    K, N = pool["k_pages"].shape[:2]
    Dh, Dp = cfg.d_head, pool["k_pages"].shape[-1]
    kp = pool["k_pages"].reshape((K * N,) + pool["k_pages"].shape[2:])
    vp = pool["v_pages"].reshape(kp.shape)
    layers, nb = params["mamba_layers"], cfg.n_mem_blocks
    hs, convs = [], []

    def body(h, inp):
        p, sh, sc = inp
        h, sh, sc = _mamba_paged(p, h, cfg, None, sh, sc, n_valid)
        return h, (sh, sc)

    for kind, a, b in plan(cfg):
        if kind == "mamba":
            lp = jax.tree.map(lambda t: t[a:b], layers)
            x, (sh, sc) = jax.lax.scan(
                body, x, (lp, pool["h"][a:b], pool["conv"][a:b]))
            hs.append(sh)
            convs.append(sc)
            continue
        sp, hp = _take(params["shared"], b % nb), _take(params["hybrid"], b)
        tab = tables + b * N
        with jax.named_scope("paged.shared"):
            u = jnp.concatenate([x, e], axis=-1)
            u = rmsnorm(sp["ln_in"], u, cfg.norm_eps)
        with jax.named_scope("paged.qkv"):
            q, k, v = attn._project_qkv(sp["attn"], u, cfg, positions)
            # heads zero-padded to whole vregs, as the pool holds them, and
            # the scores scaled by 1 / sqrt(Dh / 2)
            q, k, v = attn.pad_heads(q, k, v, Dp, scale=2.0)
        with jax.named_scope("paged.kv_write"):
            kp, vp = attn.paged_kv_write_pages(kp, vp, k, v, tab, q_start,
                                               n_valid)
        with jax.named_scope("paged.attention"):
            from repro.kernels.paged_attention import ops as paged_ops
            o = paged_ops.paged_attention(q, kp, vp, tab, q_start,
                                          n_valid)[..., :Dh]
            a_out = o.astype(x.dtype).reshape(B, S, cfg.attn_inner) \
                @ sp["attn"]["wo"].astype(x.dtype)
        with jax.named_scope("paged.shared"):
            t = _shared_mlp(sp, hp, a_out, cfg)
        x, sh, sc = _mamba_paged(_take(layers, a), x, cfg, t, pool["h"][a],
                                 pool["conv"][a], n_valid)
        hs.append(sh[None])
        convs.append(sc[None])

    pool = {"k_pages": kp.reshape(pool["k_pages"].shape),
            "v_pages": vp.reshape(pool["v_pages"].shape),
            "h": jnp.concatenate(hs, 0),
            "conv": jnp.concatenate(convs, 0).astype(pool["conv"].dtype)}
    with jax.named_scope("paged.head"):
        x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
        head = _head(params)
        if logits_mode == "all":
            return x @ head.astype(x.dtype), pool
        last = jnp.clip(n_valid - 1, 0, S - 1)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        return (x @ head.astype(x.dtype))[:, 0], pool
