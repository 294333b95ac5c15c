"""GQA attention with RoPE / M-RoPE, KV cache, and an optional fused
flash-attention (Pallas) path for training/prefill.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding_rules import constrain
from repro.models.layers import apply_rope, dense_init, mrope_sections_for

NEG_INF = -1e30


def attn_init(key, cfg: ModelConfig, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, cfg.d_model, cfg.attn_inner, dtype),
        "wk": dense_init(kk, cfg.d_model, cfg.kv_inner, dtype),
        "wv": dense_init(kv, cfg.d_model, cfg.kv_inner, dtype),
        "wo": dense_init(ko, cfg.attn_inner, cfg.d_model, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.attn_inner,), dtype)
        p["bk"] = jnp.zeros((cfg.kv_inner,), dtype)
        p["bv"] = jnp.zeros((cfg.kv_inner,), dtype)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, cfg.n_heads, cfg.d_head)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    if cfg.rope:
        sections = mrope_sections_for(int(cfg.d_head * cfg.rope_pct)) if cfg.mrope else None
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_pct, sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_pct, sections)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    return q, k, v


def _sdpa(q, k, v, causal: bool, q_offset) -> jax.Array:
    """Reference scaled-dot-product attention with GQA head grouping.

    q: (B, Sq, Hq, Dh); k, v: (B, Sk, Hkv, Dh). q_offset: position of q[0]
    within the kv sequence (for decode: Sk-1 typically).
    """
    B, Sq, Hq, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, group, Dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    scores = scores / (Dh ** 0.5)
    if causal:
        qpos = q_offset + jnp.arange(Sq)
        kpos = jnp.arange(Sk)
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    return out.reshape(B, Sq, Hq, Dh)


def attn_apply(p, x, cfg: ModelConfig, positions, cache: Optional[dict] = None,
               use_flash: bool = False):
    """Returns (out, new_cache). cache = {'k','v': (B, S_max, Hkv, Dh),
    'len': ()} — decode updates in place at position ``len``."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions)

    if cache is not None:
        idx = cache["len"]
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
        new_cache = {"k": ck, "v": cv, "len": idx + S}
        if S > 1:
            # initial prefill (idx == 0 by construction): flash self-attention
            # over the incoming chunk — never materializes S^2 scores.
            from repro.kernels.flash_attention import ops as flash_ops
            out = flash_ops.flash_attention(q, k, v, causal=cfg.causal)
        else:
            kv_len = idx + S
            kpos = jnp.arange(ck.shape[1])
            valid = kpos < kv_len
            out = _sdpa_masked(q, ck, cv, cfg.causal, idx, valid)
        out = out.reshape(B, S, cfg.attn_inner)
        return out @ p["wo"].astype(x.dtype), new_cache

    if use_flash:
        from repro.kernels.flash_attention import ops as flash_ops
        out = flash_ops.flash_attention(q, k, v, causal=cfg.causal)
    else:
        out = _sdpa(q, k, v, cfg.causal, q_offset=0)
    out = out.reshape(B, S, cfg.attn_inner)
    out = constrain(out, "batch", None, "heads")
    return out @ p["wo"].astype(x.dtype), None


def _sdpa_masked(q, k, v, causal, q_offset, valid_k):
    B, Sq, Hq, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, group, Dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(q.dtype)).astype(jnp.float32)
    scores = scores / (Dh ** 0.5)
    mask = valid_k[None, :]
    if causal:
        qpos = q_offset + jnp.arange(Sq)
        kpos = jnp.arange(Sk)
        mask = mask & (qpos[:, None] >= kpos[None, :])
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(q.dtype))
    return out.reshape(B, Sq, Hq, Dh)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_entries: int,
                  dtype=jnp.bfloat16) -> dict:
    """Stacked KV cache for ``n_entries`` attention invocations (layers)."""
    shape = (n_entries, batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "len": jnp.zeros((n_entries,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# paged KV (serving): per-slot block tables over a shared page pool


def lanes(n: int) -> int:
    """``n`` rounded up to whole 128-lane vregs."""
    return -(-n // 128) * 128


def init_paged_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                    n_entries: int, dtype=jnp.float32) -> dict:
    """Shared page pool for ``n_entries`` layers: requests own disjoint sets
    of pages via block tables instead of contiguous per-request caches, so a
    finished request's pages recycle into any slot (after the in-kernel
    zeroing — see kernels/paged_attention). Pages are head-major,
    (N, Hkv, P, D) per layer, so one page's kv heads are contiguous (P, D)
    tiles. D is the head width zero-padded to whole 128-lane vregs: the
    kernel copies pages by id from the pool in HBM, and the TPU compiler
    refuses such a copy of a slice narrower than its 128-lane tiles (heads
    of 96 then take a third more pool memory)."""
    shape = (n_entries, n_pages, cfg.n_kv_heads, page_size, lanes(cfg.d_head))
    return {"k_pages": jnp.zeros(shape, dtype), "v_pages": jnp.zeros(shape, dtype)}


def pad_heads(q, k, v, d_pad: int, scale: float = 1.0):
    """Zero-pad q, k, v (..., Dh) to the pool's ``d_pad`` lanes. The kernel
    divides scores by sqrt(d_pad), so q carries sqrt(scale * d_pad / Dh):
    the scores come out as q.k / sqrt(Dh / scale), the model's own with
    ``scale`` 1."""
    Dh = q.shape[-1]
    if d_pad == Dh and scale == 1.0:
        return q, k, v
    pad = ((0, 0),) * (q.ndim - 1) + ((0, d_pad - Dh),)
    q = (q * (scale * d_pad / Dh) ** 0.5).astype(k.dtype)
    return jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)


def paged_kv_write(k_pages, v_pages, k, v, tables, q_start, n_valid):
    """Scatter a (B, C, Hkv, Dh) chunk of fresh K/V into the slots' own
    pages. Rows past ``n_valid`` scatter to page id N (one past the pool) and
    are dropped, so inactive slots and prompt padding write nothing."""
    N, P = k_pages.shape[0], k_pages.shape[2]
    B, C = k.shape[0], k.shape[1]
    pos = q_start[:, None] + jnp.arange(C)[None, :]            # (B, C)
    valid = jnp.arange(C)[None, :] < n_valid[:, None]
    page = jnp.take_along_axis(tables, jnp.clip(pos // P, 0, tables.shape[1] - 1),
                               axis=1)
    page = jnp.where(valid, page, N)                           # OOB -> dropped
    off = pos % P
    # the (B, C) index arrays around the head slice put (B, C) first, so the
    # indexed view is (B, C, Hkv, Dh): the chunk's own layout
    k_pages = k_pages.at[page, :, off].set(k.astype(k_pages.dtype), mode="drop")
    v_pages = v_pages.at[page, :, off].set(v.astype(v_pages.dtype), mode="drop")
    return k_pages, v_pages


def paged_kv_write_pages(k_pages, v_pages, k, v, tables, q_start, n_valid):
    """As ``paged_kv_write``, by whole pages: gather the pages the chunk
    touches, merge the chunk's valid rows into them, and scatter the pages
    back along the pool's page axis only. A scatter that indexes the page
    and the in-page offset makes XLA relayout the whole (N, Hkv, P, Dh) pool
    around it (a copy of every page, each call); this one moves the touched
    pages alone. Zamba2-7B's 12-layer serving step over 8 slots of 4,096
    tokens needs 2.1 GB of temporaries with this write and 6.6 GB with
    ``paged_kv_write``, which then overflows a TPU v5e's 16 GB by 1.6 GB
    (XLA's account for a described v5e). A page holding no valid row is
    dropped (sent to id N), so no page is written twice in one call."""
    N, H, P, D = k_pages.shape
    B, C = k.shape[0], k.shape[1]
    nP = 1 if C == 1 else -(-C // P) + 1        # pages a chunk can touch
    j = q_start[:, None] // P + jnp.arange(nP)[None, :]        # (B, nP)
    page = jnp.take_along_axis(tables, jnp.clip(j, 0, tables.shape[1] - 1),
                               axis=1)
    pos = j[:, :, None] * P + jnp.arange(P)[None, None, :]     # (B, nP, P)
    c = pos - q_start[:, None, None]
    new = (c >= 0) & (c < n_valid[:, None, None])
    page = jnp.where(new.any(-1), page, N)                     # OOB -> dropped
    c = jnp.clip(c, 0, C - 1)

    def merge(pages, rows):
        old = pages.at[page].get(mode="fill", fill_value=0)    # (B,nP,H,P,D)
        fresh = jax.vmap(lambda r, i: r[i])(rows, c.reshape(B, -1))
        fresh = fresh.reshape(B, nP, P, H, D).transpose(0, 1, 3, 2, 4)
        out = jnp.where(new[:, :, None, :, None],
                        fresh.astype(pages.dtype), old)
        return pages.at[page].set(out, mode="drop")

    return merge(k_pages, k), merge(v_pages, v)


def attn_apply_paged(p, x, cfg: ModelConfig, positions, k_pages, v_pages,
                     tables, q_start, n_valid):
    """Paged-cache attention step: write the chunk's K/V through the block
    table, then read the slot's live pages back through the paged kernel.
    Returns (out, k_pages, v_pages). The write precedes the read, so query
    row c at position q_start + c sees itself (mask ``kvpos <= q_start +
    c``). Heads are zero-padded to the pool's lane width around the kernel.

    Named scopes for a profile: ``paged.qkv`` (the projections),
    ``paged.kv_write`` and ``paged.attention`` (the paged kernel and the
    output projection)."""
    from repro.kernels.paged_attention import ops as paged_ops

    B, S, _ = x.shape
    with jax.named_scope("paged.qkv"):
        q, k, v = _project_qkv(p, x, cfg, positions)
        q, k, v = pad_heads(q, k, v, k_pages.shape[-1])
    with jax.named_scope("paged.kv_write"):
        k_pages, v_pages = paged_kv_write(k_pages, v_pages, k, v, tables,
                                          q_start, n_valid)
    with jax.named_scope("paged.attention"):
        out = paged_ops.paged_attention(q, k_pages, v_pages, tables, q_start,
                                        n_valid)[..., :cfg.d_head]
        out = out.astype(x.dtype).reshape(B, S, cfg.attn_inner)
        out = out @ p["wo"].astype(x.dtype)
    return out, k_pages, v_pages
