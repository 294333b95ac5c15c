"""Zero-sum DP masking (paper §4.2) over gradient pytrees.

Two constructions, numerically interchangeable in aggregate:

* ``admin`` (paper-faithful): the admin draws u_1..u_{n-1} iid wide-spread
  noise and sets m_n = xi - sum(u_i), with xi ~ N(0, (sigma*C)^2 I). Masks are
  O(P) tensors the admin must ship to each silo every step.
* ``pairwise`` (beyond-paper, DESIGN.md §2): m_i = B(r_i - r_{(i+1) mod n})
  + xi_i with xi_i ~ N(0, (sigma*C)^2/n I), all streams derived from 32-byte
  per-step keys. Telescoping gives sum_i m_i = xi exactly; each silo only
  needs its subkeys. The fused kernel (kernels/dp_fused) regenerates masks
  in VMEM so they never touch HBM.

Both satisfy the paper's three properties: (1) aggregate == DP-SGD noise,
(2) each masked gradient is marginally wide-spread noise, (3) collusion of
n-1 owners still leaves g_i + xi on the honest silo.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dp_fused import ops as fused_ops


def _raw(key: jax.Array) -> jax.Array:
    """(2,) uint32 view of a jax PRNG key."""
    if hasattr(key, "dtype") and jnp.issubdtype(key.dtype, jnp.uint32):
        return key
    return jax.random.key_data(key).astype(jnp.uint32)


# ---------------------------------------------------------------------------
# Pairwise masks (key-derived, zero distribution traffic)
#
# The whole pytree is flattened into one flat buffer (core/flatbuf) and the
# mask regenerated in a single fused dispatch with *global packed indices*
# as threefry counters: the same streams the barrier and wire tiers' fused
# clip+mask kernel draws.


def pairwise_mask_tree(grads, key_r, key_xi, silo, n_silos: int, sigma_c,
                       b_scale: float, impl: str = "auto"):
    """Apply m_silo to every leaf of ``grads``.
    silo may be a traced scalar (lax.axis_index); keys are per-step.
    ``impl`` picks the packed kernel's inner variant."""
    return fused_ops.packed_mask_tree(grads, _raw(key_r), _raw(key_xi), silo,
                                      n_silos, sigma_c, b_scale, impl=impl)


def pairwise_mask_only(shapes_tree, key_r, key_xi, silo, n_silos: int,
                       sigma_c, b_scale: float, impl: str = "auto"):
    zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), shapes_tree)
    return pairwise_mask_tree(zeros, key_r, key_xi, silo, n_silos, sigma_c,
                              b_scale, impl=impl)


# ---------------------------------------------------------------------------
# Admin-generated masks (paper-faithful wire protocol)


def admin_masks(key: jax.Array, template, n_silos: int, sigma_c, b_scale: float,
                active=None, correction=None):
    """Generate the full set of n masks (stacked on a leading silo axis) such
    that sum_i m_i = xi ~ N(0, sigma_c^2 I). This is the O(n * P) object the
    paper's admin distributes; DPPipeline's ``mask_mode='admin'`` runs the
    faithful baseline through the shared stage graph on top of it.

    ``active``: optional (n_silos,) participation set — dropped silos get
    zero masks and the *last active* silo closes the sum, so the active
    masks still telescope to xi for any subset. ``correction``: optional
    tree folded into the closing row (the admin-owned noise-correction term
    ``-lam*xi_{t-1}``; the admin generates every mask centrally, so the
    correction rides in the masks rather than per-silo shares)."""
    ku, kxi = jax.random.split(key)
    leaves, treedef = jax.tree.flatten(template)
    kus = jax.random.split(ku, len(leaves))
    kxis = jax.random.split(kxi, len(leaves))
    corr_leaves = jax.tree.leaves(correction) if correction is not None \
        else [None] * len(leaves)

    # one construction for every case (active=None = all silos), drawing
    # each u row from its own subkey — the SAME streams admin_mask_row uses,
    # so a handler reconstructing only its row stays consistent with the
    # distributed set
    act = jnp.ones((n_silos,), jnp.float32) if active is None \
        else jnp.asarray(active, jnp.float32)
    # the closing row is the last *active* silo (argmax finds the first
    # max of the reversed gates = the last set bit)
    closing = n_silos - 1 - jnp.argmax(act[::-1])
    onehot = (jnp.arange(n_silos) == closing).astype(jnp.float32)

    def per_leaf(ku, kxi, leaf, corr):
        shape_1 = (n_silos,) + (1,) * leaf.ndim
        row_keys = jax.random.split(ku, n_silos)
        u = jax.vmap(lambda k: jax.random.normal(k, leaf.shape,
                                                 jnp.float32))(row_keys)
        u = u * b_scale * act.reshape(shape_1)
        xi = jax.random.normal(kxi, leaf.shape, jnp.float32) * sigma_c
        if corr is not None:
            xi = xi - corr.astype(jnp.float32)
        # sequential subtraction in index order — the identical fp
        # association admin_mask_row uses, so single rows reconstruct
        # bit-equal (gated terms subtract exact zeros)
        close_row = xi
        for i in range(n_silos):
            close_row = close_row - u[i] * (1.0 - onehot[i])
        oh = onehot.reshape(shape_1)
        return u * (1.0 - oh) + oh * close_row[None]

    return jax.tree.unflatten(
        treedef, [per_leaf(a, b, l, c)
                  for a, b, l, c in zip(kus, kxis, leaves, corr_leaves)])


def admin_mask_row(key: jax.Array, template, n_silos: int, silo: int, sigma_c,
                   b_scale: float, active=None, correction=None):
    """One silo's row of the :func:`admin_masks` set (identical streams),
    without materializing the stack: O(P) for a non-closing silo, O(k*P)
    for the closing one — so n handlers each fetching their own row cost
    O(n*P) total, exactly the admin's distribution cost in the paper.
    Requires a *concrete* ``silo``/``active`` (the wire tier's case; traced
    callers use the stacked construction)."""
    silo = int(silo)
    act = np.ones(n_silos, bool) if active is None \
        else np.asarray(active).astype(bool)
    closing = int(n_silos - 1 - np.argmax(act[::-1]))
    ku, kxi = jax.random.split(key)
    leaves, treedef = jax.tree.flatten(template)
    kus = jax.random.split(ku, len(leaves))
    kxis = jax.random.split(kxi, len(leaves))
    corr_leaves = jax.tree.leaves(correction) if correction is not None \
        else [None] * len(leaves)

    def per_leaf(ku_l, kxi_l, leaf, corr):
        row_keys = jax.random.split(ku_l, n_silos)
        if silo != closing:
            u = jax.random.normal(row_keys[silo], leaf.shape, jnp.float32)
            return u * b_scale * float(act[silo])
        xi = jax.random.normal(kxi_l, leaf.shape, jnp.float32) * sigma_c
        if corr is not None:
            xi = xi - corr.astype(jnp.float32)
        for i in range(n_silos):
            if act[i] and i != closing:
                xi = xi - jax.random.normal(row_keys[i], leaf.shape,
                                            jnp.float32) * b_scale
        return xi

    return jax.tree.unflatten(
        treedef, [per_leaf(a, b, l, c)
                  for a, b, l, c in zip(kus, kxis, leaves, corr_leaves)])


def admin_xi(key: jax.Array, template, sigma_c):
    """Just the xi streams of the admin construction (same key-split
    structure as :func:`admin_masks`), so the central tiers and the
    lambda-correction can regenerate the exact aggregate noise the masks
    telescope to."""
    _, kxi = jax.random.split(key)
    leaves, treedef = jax.tree.flatten(template)
    kxis = jax.random.split(kxi, len(leaves))
    return jax.tree.unflatten(
        treedef, [jax.random.normal(k, l.shape, jnp.float32) * sigma_c
                  for k, l in zip(kxis, leaves)])


def apply_admin_mask(grads, masks, silo: int):
    """Silo-side: g_i + m_i (mask row ``silo`` of the stacked masks)."""
    return jax.tree.map(
        lambda g, m: (g.astype(jnp.float32) + m[silo]).astype(g.dtype),
        grads, masks)


# ---------------------------------------------------------------------------
# Integer-ring masking (exact cancellation; composes with int8 compression)

RING_SCALE_BITS = 16


def to_ring(x: jax.Array, clip: float) -> jax.Array:
    """Quantize fp values in [-clip, clip] to int32 fixed point."""
    scale = (1 << RING_SCALE_BITS) / clip
    return jnp.round(jnp.clip(x, -clip, clip) * scale).astype(jnp.int32)


def from_ring(x: jax.Array, clip: float) -> jax.Array:
    scale = (1 << RING_SCALE_BITS) / clip
    return x.astype(jnp.float32) / scale


def ring_mask_tree(grads_int, key, silo, n_silos: int):
    """Pairwise telescoping masks drawn uniformly from the int32 ring: the sum
    over silos wraps to exactly zero (no fp cancellation error). DP noise is
    added separately (fp) after aggregation on this path."""
    kr = _raw(key)
    leaves, treedef = jax.tree.flatten(grads_int)
    out = []
    for i, g in enumerate(leaves):
        ki = jax.random.wrap_key_data(kr + jnp.uint32(0x9E3779B9) * jnp.uint32(i + 1))
        nxt = (silo + 1) % n_silos
        r_i = jax.random.bits(jax.random.fold_in(ki, silo), g.shape, jnp.uint32)
        r_n = jax.random.bits(jax.random.fold_in(ki, nxt), g.shape, jnp.uint32)
        mask = (r_i - r_n).astype(jnp.int32)  # wraps mod 2^32
        out.append(g + mask)
    return jax.tree.unflatten(treedef, out)
