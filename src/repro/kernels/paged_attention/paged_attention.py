"""Paged attention over a block-table-indirected KV page pool (Pallas).

Serving reads K/V through a per-slot *block table* — a row of physical page
ids — instead of a contiguous per-request cache, so a finished request's
pages can be recycled into any other slot. Isolation is enforced in the
kernel, twice over:

* the attention kernel can only touch pages named in the first live entries
  of the slot's own table row: each copy's page id is read from that row in
  SMEM at a column clamped to the slot's last live page, so there is no
  base+offset arithmetic that could wander into another slot's pages, and
  the table entries past the live ones (padding, stale ids) are never
  read; the per-slot length mask clips reads to positions the slot has
  written;
* ``paged_reset`` zeroes a slot's pages *in-kernel* on admission
  (``input_output_aliases`` makes it an in-place write on TPU), so a freshly
  admitted request's attention output is bit-equal to a fresh-cache run by
  construction — whatever a previous tenant left in those pages is gone
  before the first read.

The walk: slot ``b`` has ``live_pages`` = ceil((q_start + n_valid) / P) live
pages (none when ``n_valid == 0``). A grid step takes one slot and a group
of ``block_heads`` kv heads and loops over the slot's live pages only, in
blocks of ``block_pages`` pages (at least 128 tokens where the table is that
long), copying each block's pages by id from the pool in HBM into a
double-buffered VMEM block while the previous block computes. Both sizes
come from the call's shapes; a slot with no live pages copies nothing and
writes zeros.

Bit-identity contract: ``_block_step`` and ``_mask`` below are shared
*verbatim* by the Pallas kernel body and the jnp oracle (``ref.py``), which
also walks the same blocks of the same page ids, so both trace to the same
XLA ops and the parity tests can assert bitwise equality, not just allclose
(XLA contracts mul+add chains into FMA under jit; two textually different
formulations of the same recurrence diverge by 1 ulp).

Layouts:
  q                (B, C, Hq, D)   — C = chunk of new tokens per slot
  k_pages/v_pages  (N, Hkv, P, D)  — one layer's pool: N pages of P tokens,
                                     head-major so one page's kv heads are
                                     contiguous (P, D) tiles (TPU (8, 128))
  tables           (B, nP) int32   — per-slot physical page ids
  q_start          (B,)    int32   — tokens already in the slot's cache
                                     (q row c sits at position q_start + c)
  n_valid          (B,)    int32   — new tokens of the slot in this call
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_TOKENS = 128        # kv tokens a block aims for: a full lane width
ROWS_PER_STEP = 256       # query rows (heads x folded rows) a step aims for
VMEM_BUDGET = 16 << 20    # what one grid step's blocks may take in VMEM
VMEM_LIMIT = 32 << 20     # the kernel's scoped VMEM (a v5e core has 128 MiB)


def _vmem_bytes(k, heads, rows, d_head, itemsize, page_size):
    """VMEM one grid step holds: the double-buffered K and V blocks, the q
    and out blocks (double-buffered by the pipeline), the accumulators, and
    a few (rows, k*P) score tiles of one head."""
    rows = max(rows, 2)
    kv = 2 * 2 * k * page_size * heads * d_head * itemsize
    qo = 4 * heads * rows * d_head * 4
    acc = heads * rows * (d_head + 2) * 4
    return kv + qo + acc + 4 * rows * k * page_size * 4


def block_pages(rows, d_head, itemsize, page_size, n_pages):
    """Pages per block of the walk, from the call's shapes: enough for
    ``BLOCK_TOKENS`` kv tokens, no more than the table holds, fewer while one
    head's step would not fit ``VMEM_BUDGET``. ``rows``: query rows per kv
    head (``Hq // Hkv * C``). The serving scheduler counts the pages the
    walk visits with this same function."""
    k = max(1, min(n_pages, -(-BLOCK_TOKENS // page_size)))
    while k > 1 and _vmem_bytes(k, 1, rows, d_head, itemsize,
                                page_size) > VMEM_BUDGET:
        k -= 1
    return k


def block_heads(n_kv_heads, rows, d_head, itemsize, page_size, k):
    """Kv heads per grid step: the most (a divisor of ``n_kv_heads``) whose
    query rows stay within ``ROWS_PER_STEP`` and whose blocks fit
    ``VMEM_BUDGET`` — all of a page's heads for MHA decode, one for long
    GQA chunks. A page's heads are contiguous in the pool, so one copy per
    page still moves them all."""
    h = n_kv_heads
    while h > 1 and (n_kv_heads % h or h * max(rows, 2) > ROWS_PER_STEP
                     or _vmem_bytes(k, h, rows, d_head, itemsize,
                                    page_size) > VMEM_BUDGET):
        h -= 1
    return h


def live_pages(q_start, n_valid, page_size, n_pages):
    """Table pages holding each slot's tokens after the call: ceil((q_start
    + n_valid) / P), clipped to the table; 0 for a slot with no new rows."""
    n = -(-(q_start + n_valid) // page_size)
    return jnp.where(n_valid > 0, jnp.clip(n, 0, n_pages), 0)


def _block_step(q, k, v, m, l, acc, mask, sm_scale):
    """One block of the online-softmax recurrence — shared verbatim by the
    Pallas kernel body and the jnp oracle, so both trace to the same ops.
    q: (rows, D); k, v: (k*P, D); mask: (rows, k*P)."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _mask(q_start_b, j, block, chunk, GC):
    """Causal+length mask for block ``j`` (``block`` kv tokens) against the
    folded (G*C, block) score tile: row r is query chunk-token ``r mod
    chunk`` at absolute position ``q_start + r mod chunk``; kv column col is
    absolute ``j*block + col``."""
    r = jax.lax.broadcasted_iota(jnp.int32, (GC, block), 0)
    c = jax.lax.rem(r, chunk)
    kvpos = j * block + jax.lax.broadcasted_iota(jnp.int32, (GC, block), 1)
    return kvpos <= q_start_b + c


def block_ids(entry, j, k, live):
    """Page ids of block ``j`` of a slot's table row: columns ``j*k ..
    j*k+k-1``, each clamped to the last live one (``live >= 1``), so a block
    that ends past the slot's live pages repeats its last live page — masked
    out — instead of reading a table entry past them. ``entry(c)`` reads
    column ``c`` of the row (the kernel's SMEM table, the oracle's array)."""
    return [entry(jnp.minimum(j * k + i, live - 1)) for i in range(k)]


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

    A one-row score tile makes ``_block_step``'s dots rank-1, and XLA lowers
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


def _paged_kernel(live_ref, tables_ref, qstart_ref, q_ref, k_hbm, v_hbm,
                  o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr, *,
                  page_size, block, heads, chunk, gc, sm_scale):
    b = pl.program_id(0)
    h0 = pl.program_id(1) * heads
    live = live_ref[b]
    n_blocks = (live + block - 1) // block
    D = k_buf.shape[-1]

    def copies(j, slot):
        ids = block_ids(lambda c: tables_ref[b, c], j, block, live)
        return [pltpu.make_async_copy(pool.at[page, pl.ds(h0, heads)],
                                      buf.at[slot, i], sems.at[t, slot])
                for i, page in enumerate(ids)
                for t, (pool, buf) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf)))]

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(n_blocks > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def body(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_blocks)
        def _next():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        mask = _mask(qstart_ref[b], j, block * page_size, chunk, gc)
        for h in range(heads):
            q = q_ref[0, h].astype(jnp.float32)                  # (gc, D)
            k = k_buf[slot, :, h].reshape(block * page_size, D) \
                .astype(jnp.float32)
            v = v_buf[slot, :, h].reshape(block * page_size, D) \
                .astype(jnp.float32)
            m, l, acc = _block_step(q, k, v, m_scr[h], l_scr[h], acc_scr[h],
                                    mask, sm_scale)
            m_scr[h], l_scr[h], acc_scr[h] = m, l, acc
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)
    # a slot with no live pages keeps acc = 0, l = 0: its rows come out as
    # zeros, finite, never a NaN that could poison the shared graph
    o_ref[0] = (acc_scr[...] /
                jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(q, k_pages, v_pages, tables, q_start, n_valid,
                           interpret=False):
    """Block-table paged attention; returns fp32 (B, C, Hq, D).

    The grid walks (slot, group of kv heads); each step loops over the
    slot's live pages in blocks, copying each page by the id its own table
    row names, so the kernel's reachable memory is exactly the slot's live
    pages. On the TPU, D is a whole number of 128-lane vregs: the compiler
    copies no narrower slice of the pool (``attention.init_paged_pool``
    pads the heads)."""
    B, C, Hq, D = q.shape
    _, Hkv, P, _ = k_pages.shape
    nP = tables.shape[1]
    qt, GC, GCp = _fold_padded(q, B, C, Hq, Hkv, D)
    item = k_pages.dtype.itemsize
    k = block_pages(GC, D, item, P, nP)
    hb = block_heads(Hkv, GC, D, item, P, k)
    live = live_pages(q_start, n_valid, P, nP).astype(jnp.int32)
    qo_spec = pl.BlockSpec((1, hb, GCp, D),
                           lambda b, h, *_: (b, h, 0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, k, hb, P, D), k_pages.dtype)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=P, block=k, heads=hb,
                          chunk=C, gc=GCp, sm_scale=1.0 / D ** 0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, Hkv // hb),
            in_specs=[qo_spec, any_spec, any_spec], out_specs=qo_spec,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((hb, GCp, 1), jnp.float32),
                            pltpu.VMEM((hb, GCp, 1), jnp.float32),
                            pltpu.VMEM((hb, GCp, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, GCp, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(live, tables, q_start, qt, k_pages, v_pages)
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
