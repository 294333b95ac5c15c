"""Reference implementations for the paged-attention kernels.

``paged_attention_oracle`` mirrors the Pallas kernel block-for-block with the
*shared* ``_block_step``/``_mask`` helpers, the same live bound and the same
page ids per block, and runs fully jitted, so the parity tests assert bitwise
equality (see the bit-identity contract in ``paged_attention.py``).
``paged_attention_gather`` is the production compiled-CPU path: one gather +
one materialized softmax, numerically equivalent but not bit-identical to
the online-softmax recurrence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.paged_attention import (NEG_INF,
                                                           _block_step,
                                                           _fold_padded,
                                                           _mask, _unfold,
                                                           block_ids,
                                                           block_pages,
                                                           live_pages)


@jax.jit
def paged_attention_oracle(q, k_pages, v_pages, tables, q_start, n_valid):
    """The kernel's walk as python loops over (slot, kv head) around a loop
    over the slot's live blocks, inside one jit — same helpers, same page
    ids, same op sequence, bit-equal output. Test-sized pools only (compile
    time grows with slots x heads)."""
    B, C, Hq, D = q.shape
    _, Hkv, P, _ = k_pages.shape
    nP = tables.shape[1]
    qt, GC, GCp = _fold_padded(q, B, C, Hq, Hkv, D)
    sm_scale = 1.0 / D ** 0.5
    k = block_pages(GC, D, k_pages.dtype.itemsize, P, nP)
    live = live_pages(q_start, n_valid, P, nP)

    res = []
    for b in range(B):
        heads = []
        for h in range(Hkv):
            qf = qt[b, h].astype(jnp.float32)

            def body(j, carry, b=b, h=h, qf=qf):
                ids = jnp.stack(block_ids(lambda c: tables[b, c], j, k,
                                          live[b]))
                kb = k_pages[ids, h].reshape(k * P, D).astype(jnp.float32)
                vb = v_pages[ids, h].reshape(k * P, D).astype(jnp.float32)
                mask = _mask(q_start[b], j, k * P, C, GCp)
                return _block_step(qf, kb, vb, *carry, mask, sm_scale)

            m, l, acc = jax.lax.fori_loop(
                0, (live[b] + k - 1) // k, body,
                (jnp.full((GCp, 1), NEG_INF, jnp.float32),
                 jnp.zeros((GCp, 1), jnp.float32),
                 jnp.zeros((GCp, D), jnp.float32)))
            heads.append((acc / jnp.maximum(l, 1e-30))[:GC])
        res.append(jnp.stack(heads))
    return _unfold(jnp.stack(res), B, C, Hq, Hkv, D)


@jax.jit
def paged_attention_gather(q, k_pages, v_pages, tables, q_start, n_valid):
    """Vectorized jnp path: gather the slot's live pages into a contiguous
    (B, nP*P) view (the positions past them zeroed, their table entries
    never read), then one masked GQA softmax. O(nP*P) score memory per
    query — fine for serving-sized pools, and XLA fuses the gather."""
    B, C, Hq, D = q.shape
    _, Hkv, P, _ = k_pages.shape
    nP = tables.shape[1]
    G = Hq // Hkv
    S = nP * P
    live = live_pages(q_start, n_valid, P, nP)
    cols = jnp.clip(jnp.minimum(jnp.arange(nP)[None], live[:, None] - 1), 0)
    ids = jnp.take_along_axis(tables, cols, axis=1)
    kvpos = jnp.arange(S)
    kept = (kvpos[None] < live[:, None] * P)[:, :, None, None]   # (B,S,1,1)

    def gather(pages):  # (B, nP, Hkv, P, D) -> (B, nP*P, Hkv, D)
        g = pages[ids].transpose(0, 1, 3, 2, 4).reshape(B, S, Hkv, D)
        return jnp.where(kept, g.astype(jnp.float32), 0.0)

    k = gather(k_pages)
    v = gather(v_pages)
    qg = q.reshape(B, C, Hkv, G, D).astype(jnp.float32)
    scores = jnp.einsum("bchgd,bkhd->bhgck", qg, k) * (1.0 / D ** 0.5)
    qpos = q_start[:, None] + jnp.arange(C)[None, :]          # (B, C)
    mask = kvpos[None, None] <= qpos[:, :, None]              # (B, C, S)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    # NEG_INF is finite: fully-masked rows come out as a finite average the
    # host ignores, and a slot with no live pages averages zeros
    out = jnp.einsum("bhgck,bkhd->bchgd", p / jnp.maximum(l, 1e-30), v)
    return out.reshape(B, C, Hq, D)


@jax.jit
def paged_reset_ref(k_pages, v_pages, row):
    """Zero block-table row ``row``'s pages in the stacked (L, N, H, P, D)
    pools. Duplicate page ids in the row are fine (idempotent zero)."""
    return (k_pages.at[:, row].set(0.0), v_pages.at[:, row].set(0.0))


@jax.jit
def paged_rollback_ref(k_pages, v_pages, row, bounds):
    """Zero logical token positions ``[bounds[0], bounds[1])`` of block-table
    row ``row`` in the stacked (L, N, H, P, D) pools.

    Implemented as a scatter-*multiply* by a 0/1 keep mask rather than a
    gather/where/set round-trip: a short row pads with duplicate page ids,
    and with ``set`` the duplicate write (whose logical positions are all
    past ``end``, hence unmasked) could race the real write and resurrect
    zeroed lanes. Multiplies compose — the pad visit is a multiply-by-one
    no-op regardless of ordering."""
    nP = row.shape[0]
    P = k_pages.shape[3]
    pos = jnp.arange(nP)[:, None] * P + jnp.arange(P)[None, :]
    keep = (~((pos >= bounds[0]) & (pos < bounds[1]))).astype(k_pages.dtype)
    keep = keep[None, :, None, :, None]
    return (k_pages.at[:, row].multiply(keep),
            v_pages.at[:, row].multiply(keep))
