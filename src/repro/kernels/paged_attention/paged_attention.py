"""Paged attention over a block-table-indirected KV page pool (Pallas).

Serving reads K/V through a per-slot *block table* — a row of physical page
ids — instead of a contiguous per-request cache, so a finished request's
pages can be recycled into any other slot. Isolation is enforced in the
kernel, twice over:

* the attention kernel can only touch pages named in the slot's own table
  row (the scalar-prefetch index map IS the access path — there is no
  base+offset arithmetic that could wander into another slot's pages), and
  the per-slot length mask clips reads to positions the slot has written;
* ``paged_reset`` zeroes a slot's pages *in-kernel* on admission
  (``input_output_aliases`` makes it an in-place write on TPU), so a freshly
  admitted request's attention output is bit-equal to a fresh-cache run by
  construction — whatever a previous tenant left in those pages is gone
  before the first read.

Bit-identity contract: ``_page_step`` and ``_mask`` below are shared
*verbatim* by the Pallas kernel body and the jnp oracle (``ref.py``), so
both trace to the same XLA ops and the parity tests can assert bitwise
equality, not just allclose (XLA contracts mul+add chains into FMA under
jit; two textually different formulations of the same recurrence diverge
by 1 ulp).

Layouts:
  q                (B, C, Hq, D)   — C = chunk of new tokens per slot
  k_pages/v_pages  (N, Hkv, P, D)  — one layer's pool: N pages of P tokens,
                                     head-major so a kernel block is one
                                     head's (P, D) page (TPU (8, 128) tiling)
  tables           (B, nP) int32   — per-slot physical page ids
  q_start          (B,)    int32   — tokens already in the slot's cache
                                     (q row c sits at position q_start + c)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _page_step(q, k, v, m, l, acc, mask, sm_scale):
    """One page of the online-softmax recurrence — shared verbatim by the
    Pallas kernel body and the jnp oracle, so both trace to the same ops."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _mask(q_start_b, j, page_size, chunk, GC):
    """Causal+length mask for page ``j`` against the folded (G*C, P) score
    tile: row r is query chunk-token ``r mod chunk`` at absolute position
    ``q_start + r mod chunk``; kv column col is absolute ``j*P + col``."""
    r = jax.lax.broadcasted_iota(jnp.int32, (GC, page_size), 0)
    c = jax.lax.rem(r, chunk)
    kvpos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (GC, page_size), 1)
    return kvpos <= q_start_b + c


def _fold(q, B, C, Hq, Hkv, D):
    """(B, C, Hq, D) -> (B, Hkv, G*C, D): GQA query groups stacked onto the
    row axis so one kernel instance serves one kv head."""
    G = Hq // Hkv
    return q.reshape(B, C, Hkv, G, D).transpose(0, 2, 3, 1, 4) \
            .reshape(B, Hkv, G * C, D)


def _unfold(o, B, C, Hq, Hkv, D):
    G = Hq // Hkv
    return o.reshape(B, Hkv, G, C, D).transpose(0, 3, 1, 2, 4) \
            .reshape(B, C, Hq, D)


def _fold_padded(q, B, C, Hq, Hkv, D):
    """Fold, then pad the row axis to >= 2 (duplicate the single row).

    A one-row score tile makes ``_page_step``'s dots rank-1, and XLA lowers
    a rank-1 contraction through a different reduction than the matrix case
    — 1-ulp divergence that breaks the bit-identity contract between the
    kernel and the oracle. Padding only triggers for MHA decode (G == 1,
    C == 1), where the duplicate row computes the identical query; callers
    slice back to ``GC`` rows. Returns (folded, GC, padded GC)."""
    GC = (Hq // Hkv) * C
    qt = _fold(q, B, C, Hq, Hkv, D)
    if GC == 1:
        qt = jnp.concatenate([qt, qt], axis=2)
    return qt, GC, max(GC, 2)


def _paged_kernel(tables_ref, qstart_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page_size, n_pages, chunk,
                  gc, sm_scale):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)      # (gc, D) — row-padded fold
    k = k_ref[0, 0].astype(jnp.float32)      # (P, D)
    v = v_ref[0, 0].astype(jnp.float32)
    mask = _mask(qstart_ref[b], j, page_size, chunk, gc)
    m, l, acc = _page_step(q, k, v, m_scr[...], l_scr[...], acc_scr[...],
                           mask, sm_scale)
    m_scr[...], l_scr[...], acc_scr[...] = m, l, acc

    @pl.when(j == n_pages - 1)
    def _done():
        # NEG_INF is finite, so even a fully-masked row (inactive slot,
        # q_start < 0) yields a finite softmax — garbage the host ignores,
        # never a NaN that could poison the shared graph
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(q, k_pages, v_pages, tables, q_start,
                           interpret=False):
    """Block-table paged attention; returns fp32 (B, C, Hq, D).

    The grid walks (slot, kv head, page); the kv index map reads the page id
    from the scalar-prefetched table row, so the kernel's reachable memory
    is exactly the slot's own pages."""
    B, C, Hq, D = q.shape
    _, Hkv, P, _ = k_pages.shape
    nP = tables.shape[1]
    qt, GC, GCp = _fold_padded(q, B, C, Hq, Hkv, D)
    q_spec = pl.BlockSpec((1, 1, GCp, D), lambda b, h, j, t, qs: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, P, D), lambda b, h, j, t, qs: (t[b, j], h, 0, 0))
    o_spec = pl.BlockSpec((1, 1, GCp, D), lambda b, h, j, t, qs: (b, h, 0, 0))
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=P, n_pages=nP, chunk=C,
                          gc=GCp, sm_scale=1.0 / D ** 0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, Hkv, nP),
            in_specs=[q_spec, kv_spec, kv_spec], out_specs=o_spec,
            scratch_shapes=[pltpu.VMEM((GCp, 1), jnp.float32),
                            pltpu.VMEM((GCp, 1), jnp.float32),
                            pltpu.VMEM((GCp, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, GCp, D), jnp.float32),
        interpret=interpret,
    )(tables, q_start, qt, k_pages, v_pages)
    return _unfold(out[:, :, :GC], B, C, Hq, Hkv, D)


def _reset_kernel(row_ref, k_ref, v_ref, ko_ref, vo_ref):
    ko_ref[...] = jnp.zeros_like(ko_ref)
    vo_ref[...] = jnp.zeros_like(vo_ref)


def _rollback_kernel(row_ref, bounds_ref, k_ref, v_ref, ko_ref, vo_ref, *,
                     page_size):
    """Zero token positions in [start, end) of the slot's logical sequence.

    Page ``j`` of the row covers logical positions ``j*P .. j*P+P-1``; the
    mask zeroes exactly the rejected speculative tail and writes everything
    else back unchanged (the out blocks alias the in blocks, so untouched
    lanes are a no-op write of their own value)."""
    j = pl.program_id(1)
    start, end = bounds_ref[0], bounds_ref[1]
    P = page_size
    pos = j * P + jax.lax.broadcasted_iota(jnp.int32, (1, P, 1), 1)
    dead = (pos >= start) & (pos < end)
    ko_ref[0, 0] = jnp.where(dead, 0.0, k_ref[0, 0].astype(jnp.float32)) \
        .astype(ko_ref.dtype)
    vo_ref[0, 0] = jnp.where(dead, 0.0, v_ref[0, 0].astype(jnp.float32)) \
        .astype(vo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0, 1))
def paged_rollback_pallas(k_pages, v_pages, row, bounds, interpret=False):
    """Zero the K/V of logical token positions ``[bounds[0], bounds[1])`` in
    block-table row ``row`` across every layer of the stacked (L, N, H, P, D)
    pools, in place (the speculative-decoding rejected-tail eraser).

    ``row`` must be duplicate-free (unlike ``paged_reset``): a duplicate
    visit whose mask never fires writes the page's pre-zeroing content back,
    resurrecting the erased lanes. ``ops.paged_rollback`` guarantees this by
    slicing the table row down to the distinct owned pages overlapping the
    range. Inputs are donated like ``paged_reset``: callers must rebind."""
    L = k_pages.shape[0]
    nP = row.shape[0]
    spec = pl.BlockSpec((1, 1) + k_pages.shape[2:],
                        lambda l, j, row, bounds: (l, row[j], 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_rollback_kernel, page_size=k_pages.shape[3]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(L, nP),
            in_specs=[spec, spec], out_specs=[spec, spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
    )(row, bounds, k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0, 1))
def paged_reset_pallas(k_pages, v_pages, row, interpret=False):
    """Zero the pages named in block-table row ``row`` across every layer of
    the stacked (L, N, H, P, D) pools, in place (``input_output_aliases``;
    the jit donates the pools so no copy materializes). A row may repeat a
    page id — zeroing is idempotent, which lets callers pad short rows with
    their own first page instead of a reserved sentinel."""
    L = k_pages.shape[0]
    nP = row.shape[0]
    spec = pl.BlockSpec((1, 1) + k_pages.shape[2:],
                        lambda l, j, row: (l, row[j], 0, 0, 0))
    return pl.pallas_call(
        _reset_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(L, nP),
            in_specs=[spec, spec], out_specs=[spec, spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
    )(row, k_pages, v_pages)
