"""Public paged-attention API, routed through the kernel-dispatch registry.

Two kernels back the serving subsystem:

* ``paged_attention`` — block-table attention read path.
  ``impl='auto'``: Pallas on TPU; the vectorized gather path on compiled
  CPU. The unrolled jnp oracle is explicit-request only (``impl='jnp'``) —
  it exists to pin the Pallas kernel bitwise in the parity tests.
* ``paged_reset`` — in-kernel zeroing of a slot's pages on admission (the
  leak-freedom half of the contract). Pallas in-place aliasing on TPU, a
  scatter of zeros elsewhere.
* ``paged_rollback`` — in-kernel zeroing of a *position range* of a slot's
  logical sequence (the speculative-decoding rejected-tail eraser); same
  aliasing/donation contract as ``paged_reset``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.dispatch import REGISTRY, kernel_variant, on_tpu
from repro.kernels.paged_attention import ref
from repro.kernels.paged_attention.paged_attention import (
    paged_attention_pallas, paged_reset_pallas, paged_rollback_pallas)

KERNEL = "paged_attention"
RESET_KERNEL = "paged_reset"
ROLLBACK_KERNEL = "paged_rollback"


@kernel_variant(KERNEL, "pallas", priority=100,
                auto_predicate=lambda ctx: ctx["on_tpu"],
                doc="block-table Pallas kernel (interpret mode off-TPU)")
def _pallas(q, k_pages, v_pages, tables, q_start, n_valid):
    return paged_attention_pallas(q, k_pages, v_pages, tables, q_start,
                                  n_valid, interpret=not on_tpu())


@kernel_variant(KERNEL, "gather", priority=50,
                doc="vectorized gather + masked softmax (compiled CPU path)")
def _gather(q, k_pages, v_pages, tables, q_start, n_valid):
    return ref.paged_attention_gather(q, k_pages, v_pages, tables, q_start,
                                      n_valid)


@kernel_variant(KERNEL, "jnp", priority=10,
                auto_predicate=lambda ctx: False,
                doc="unrolled bit-exact oracle (explicit request only)")
def _jnp(q, k_pages, v_pages, tables, q_start, n_valid):
    return ref.paged_attention_oracle(q, k_pages, v_pages, tables, q_start,
                                      n_valid)


def paged_attention(q, k_pages, v_pages, tables, q_start, n_valid,
                    impl: str = "auto"):
    """Attention for C new tokens per slot against the slot's paged KV.

    q: (B, C, Hq, D); k_pages/v_pages: (N, Hkv, P, D); tables: (B, nP) i32;
    q_start: (B,) i32 tokens already cached (q row c reads positions
    <= q_start + c); n_valid: (B,) i32 new tokens of the slot in this call
    (its K/V already written). Only the slot's live pages, the first
    ceil((q_start + n_valid) / P) of its row, are read; a slot with
    ``n_valid == 0`` reads none and its rows come out as zeros. Returns fp32
    (B, C, Hq, D); rows past ``n_valid`` are finite and meaningless."""
    return REGISTRY.dispatch(KERNEL, impl,
                             {"C": q.shape[1], "P": k_pages.shape[2]},
                             q, k_pages, v_pages, tables, q_start, n_valid)


@kernel_variant(RESET_KERNEL, "pallas", priority=100,
                auto_predicate=lambda ctx: ctx["on_tpu"],
                doc="in-place page zeroing via input_output_aliases")
def _reset_pallas(k_pages, v_pages, row):
    return paged_reset_pallas(k_pages, v_pages, row, interpret=not on_tpu())


@kernel_variant(RESET_KERNEL, "jnp", priority=50,
                doc="scatter-of-zeros reference")
def _reset_jnp(k_pages, v_pages, row):
    return ref.paged_reset_ref(k_pages, v_pages, row)


def paged_reset(k_pages, v_pages, row, impl: str = "auto"):
    """Zero the pages in block-table row ``row`` (shape (nP,) i32) across the
    stacked (L, N, H, P, D) pools; returns the new (k_pages, v_pages).

    Treat the input pools as CONSUMED: the Pallas path donates them for the
    in-place alias, so callers must rebind (``pool = paged_reset(*pool, row)``)
    rather than keep using the old arrays."""
    return REGISTRY.dispatch(RESET_KERNEL, impl, {"nP": row.shape[0]},
                             k_pages, v_pages, row)


@kernel_variant(ROLLBACK_KERNEL, "pallas", priority=100,
                auto_predicate=lambda ctx: ctx["on_tpu"],
                doc="in-place rejected-tail zeroing via input_output_aliases")
def _rollback_pallas(k_pages, v_pages, row, bounds):
    return paged_rollback_pallas(k_pages, v_pages, row, bounds,
                                 interpret=not on_tpu())


@kernel_variant(ROLLBACK_KERNEL, "jnp", priority=50,
                doc="scatter-multiply keep-mask reference")
def _rollback_jnp(k_pages, v_pages, row, bounds):
    return ref.paged_rollback_ref(k_pages, v_pages, row, bounds)


def paged_rollback(k_pages, v_pages, row, start, end, impl: str = "auto"):
    """Zero logical token positions ``[start, end)`` of block-table row
    ``row`` across the stacked (L, N, H, P, D) pools; returns the new
    (k_pages, v_pages). Page ``j`` of the row covers positions
    ``j*P .. j*P+P-1``; ``start``/``end`` are host ints.

    The row is sliced down to exactly the pages overlapping the range before
    dispatch: rows pad short tables with duplicate page ids, and a duplicate
    visit whose mask never fires would write the page's *pre-zeroing*
    content back over the zeroed lanes (grid visits are not ordered in the
    kernel's favor). The overlapping slice contains only distinct owned
    pages, so every physical page is visited at most once. The slice length
    varies with the range (at most ceil(k/P)+1 shapes for speculative-k
    rollback), so the compile-cache cost is bounded and tiny.

    Same contract as ``paged_reset``: inputs are CONSUMED (the Pallas path
    donates them), callers must rebind."""
    start, end = int(start), int(end)
    if end <= start:
        return k_pages, v_pages
    P = k_pages.shape[3]
    sp, ep = start // P, -(-end // P)
    sub = row[sp:ep]
    bounds = jnp.asarray([start - sp * P, end - sp * P], jnp.int32)
    return REGISTRY.dispatch(ROLLBACK_KERNEL, impl, {"nP": sub.shape[0]},
                             k_pages, v_pages, sub, bounds)
