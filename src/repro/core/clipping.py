"""Gradient clipping (paper §4.3): per-example / per-microbatch / per-silo
granularities + the dynamic percentile-clipping protocol.

The masking math only requires the *per-silo contribution* to have bounded
sensitivity; per-example is the paper's DP-SGD default (feasible for the
paper's MLP3/CNN6-scale models), group granularities are the documented
adaptation for 100B-scale archs (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels.dp_fused import ops as fused_ops


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def clip_scale(norm, clip_bound) -> jax.Array:
    """DP-SGD clip factor min(1, C/||g||) — the one shared definition
    (epsilon included) so the vmap/scan/barrier paths stay in exact
    numerical agreement. ``norm`` may be a scalar or a vector of norms."""
    return jnp.minimum(1.0, clip_bound / jnp.maximum(norm, 1e-12))


def clip_tree(tree, clip_bound) -> tuple:
    """Scale the whole tree to norm <= clip_bound. Returns (tree, pre_norm)."""
    norm = global_norm(tree)
    scale = clip_scale(norm, clip_bound)
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), tree), norm


def per_example_clipped_grad(loss_fn: Callable, params, batch, clip_bound,
                             impl: str = "auto"):
    """DP-SGD per-example clipping: vmapped per-example grads, one fused
    clip-and-sum dispatch on their packed buffer (kernels/dp_fused; ``impl``
    picks its inner variant). Returns (sum_grads, per_ex_norms, mean_loss).
    ``batch`` leaves have a leading example axis."""
    def one(ex):
        return jax.value_and_grad(loss_fn)(params, jax.tree.map(lambda x: x[None], ex))

    losses, grads = jax.vmap(one)(batch)  # grads: leaves (B, ...)
    summed, norms = fused_ops.packed_clip_and_sum(grads, clip_bound,
                                                  impl=impl)
    return summed, norms, jnp.mean(losses)


def per_microbatch_clipped_grad(loss_fn: Callable, params, batch, clip_bound,
                                n_micro: int):
    """Group-level clipping: split the batch into ``n_micro`` groups, clip each
    group's mean gradient. Sensitivity bound is per-group."""
    def reshape(x):
        return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])

    mb = jax.tree.map(reshape, batch)

    def one(b):
        loss, g = jax.value_and_grad(loss_fn)(params, b)
        g, norm = clip_tree(g, clip_bound)
        return loss, g, norm

    losses, grads, norms = jax.vmap(one)(mb)
    summed = jax.tree.map(lambda g: jnp.sum(g.astype(jnp.float32), 0), grads)
    return summed, norms, jnp.mean(losses)


# ---------------------------------------------------------------------------
# Dynamic percentile clipping protocol (§4.3)

PERCENTILES = (0.1, 0.25, 0.5, 0.75, 0.9)


def masked_quantile(x: jax.Array, qs, mask: jax.Array) -> jax.Array:
    """``jnp.quantile`` (linear interpolation) restricted to ``mask``-selected
    entries; the mask may be traced (elastic participation sets). Inactive
    entries sort to +inf and never influence the result."""
    xs = jnp.sort(jnp.where(mask, x.astype(jnp.float32), jnp.inf))
    k = jnp.maximum(jnp.sum(mask.astype(jnp.int32)), 1)
    pos = jnp.asarray(qs, jnp.float32) * (k - 1).astype(jnp.float32)
    lo = jnp.floor(pos).astype(jnp.int32)
    hi = jnp.ceil(pos).astype(jnp.int32)
    frac = pos - lo.astype(jnp.float32)
    return xs[lo] * (1.0 - frac) + xs[hi] * frac


def local_percentiles(norms: jax.Array, percentiles=PERCENTILES,
                      mask: Optional[jax.Array] = None) -> jax.Array:
    """Silo-side: the norms matching the agreed percentiles (sent to admin).
    ``mask`` restricts the summary to the active silos' norms."""
    if mask is not None:
        return masked_quantile(norms, jnp.asarray(percentiles), mask)
    return jnp.quantile(norms.astype(jnp.float32), jnp.asarray(percentiles))


def select_clip_bound(all_percentiles: jax.Array, r: float, key,
                      dp_noise_scale: float = 0.0,
                      upper_bound: float = jnp.inf,
                      percentiles=PERCENTILES) -> jax.Array:
    """Admin-side: build the approximate global norm distribution from the
    silos' percentile summaries, pick the r-th percentile (+ DP noise),
    capped by the fixed upper bound (prevents unbounded noise growth).

    all_percentiles: (n_silos, len(percentiles))."""
    pooled = jnp.sort(all_percentiles.reshape(-1))
    c = jnp.quantile(pooled, r)
    if dp_noise_scale > 0.0:
        if jnp.issubdtype(key.dtype, jnp.uint32):  # raw key data
            key = jax.random.wrap_key_data(key)
        c = c + dp_noise_scale * jax.random.normal(key, ())
    return jnp.clip(c, 1e-6, upper_bound)
