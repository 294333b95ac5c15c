"""The privacy barrier (paper §4): keys and the dynamic-bound protocol.

The per-tier composition of clipping, zero-sum masking and corrected DP
noise lives in ONE place — :class:`repro.core.dp_pipeline.DPPipeline`
(stage graph ``norms -> dynamic_bound -> clip_scale -> masked_aggregate ->
corrected_noise`` with an explicit participation set). This module keeps the
pieces the engine and its callers share:

* ``BarrierKeys`` / ``step_keys`` — the admin's 32-bytes-per-step key fanout.
* ``dynamic_bound_from_percentiles`` — the §4.3 percentile-bound selection.
* ``fused_noise_packed`` — post-aggregate corrected noise on a packed
  buffer from a single stream, regenerated in VMEM from 32-byte keys.
* ``aggregate_noise_from_streams`` — test oracle for the engine's per-silo
  stream construction.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import PrivacyConfig
from repro.core import clipping, flatbuf, masking
from repro.core.noise_correction import NoiseState
from repro.kernels.dp_fused import ops as fused_ops


class BarrierKeys(NamedTuple):
    """Per-step keys owned by the admin component. 32 bytes each — the whole
    of the admin->silo 'mask distribution' traffic on the pairwise path."""
    key_r: jax.Array    # pairwise zero-sum streams
    key_xi: jax.Array   # DP noise streams (step t)
    key_clip: jax.Array  # dynamic-clipping DP noise


def step_keys(root_key, step) -> BarrierKeys:
    """Keys are carried as raw (2,) uint32 so they cross shard_map / pallas
    boundaries as plain arrays."""
    if hasattr(root_key, "dtype") and jnp.issubdtype(root_key.dtype, jnp.uint32):
        root_key = jax.random.wrap_key_data(root_key)
    k = jax.random.fold_in(root_key, step)
    kr, kx, kc = jax.random.split(k, 3)
    raw = jax.random.key_data
    return BarrierKeys(raw(kr).astype(jnp.uint32), raw(kx).astype(jnp.uint32),
                       raw(kc).astype(jnp.uint32))


# ---------------------------------------------------------------------------
# Per-silo gradient with the configured clipping granularity


def silo_grad(loss_fn, params, batch_local, priv: PrivacyConfig, clip_bound):
    """Returns (clipped_grad_sum_or_silo_grad, norms, loss). Called per-silo
    (inside shard_map) or per-microbatch-vmap (fused path)."""
    if not priv.enabled:
        loss, g = jax.value_and_grad(loss_fn)(params, batch_local)
        return g, clipping.global_norm(g)[None], loss
    if priv.clip_mode == "per_example":
        g, norms, loss = clipping.per_example_clipped_grad(
            loss_fn, params, batch_local, clip_bound)
        return g, norms, loss
    if priv.clip_mode == "per_microbatch":
        g, norms, loss = clipping.per_microbatch_clipped_grad(
            loss_fn, params, batch_local, clip_bound, n_micro=4)
        return g, norms, loss
    # per_silo: one clipped contribution per silo
    loss, g = jax.value_and_grad(loss_fn)(params, batch_local)
    g, norm = clipping.clip_tree(g, clip_bound)
    return g, norm[None], loss


# ---------------------------------------------------------------------------
# Dynamic clipping bound (§4.3) — in-graph admin protocol


def dynamic_bound_from_percentiles(percentiles_all, priv: PrivacyConfig, key):
    """percentiles_all: (n_silos, n_pct). Returns the (noisy) r-th percentile
    bound, capped (§4.3)."""
    return clipping.select_clip_bound(
        percentiles_all, priv.clip_percentile, key,
        dp_noise_scale=0.05 * priv.clip_bound,
        upper_bound=priv.clip_percentile_max)


# ---------------------------------------------------------------------------
# Post-aggregate corrected noise


def fused_noise_packed(g_packed, priv: PrivacyConfig, keys: BarrierKeys,
                       noise_state: NoiseState, clip_bound, impl: str = "auto"):
    """Corrected DP noise added directly on a packed (P,) buffer: one fused
    dispatch, noise regenerated in VMEM (n_silos=1 stream of key_xi, scale
    sigma*C; the pairwise r-terms are statically elided)."""
    sigma_c = priv.sigma * clip_bound
    lam_gate = jnp.where(noise_state.has_prev, priv.noise_lambda, 0.0)
    kx = masking._raw(keys.key_xi)
    noisy = fused_ops.clip_mask_packed(
        g_packed, 1.0, kx, kx, noise_state.prev_key, jnp.int32(0), 1,
        sigma_c, 0.0, lam_gate, use_pairwise=False,
        use_prev=priv.noise_lambda > 0.0, impl=impl)
    new_state = NoiseState(prev_key=kx, has_prev=jnp.ones((), jnp.bool_))
    return noisy, new_state


def aggregate_noise_from_streams(template, keys: BarrierKeys, n_silos: int,
                                 sigma_c):
    """Test helper: the exact sum of the packed barrier path's noise streams
    (sum_i sigma_c/sqrt(n) xi_i over the packed layout; r-terms telescope to
    zero). Bit-matches the barrier path aggregate noise."""
    layout = flatbuf.layout_of(template)
    kx = masking._raw(keys.key_xi)
    zeros = jnp.zeros((layout.total,), jnp.float32)
    total = None
    for i in range(n_silos):
        m = fused_ops.clip_mask_packed(
            zeros, 1.0, kx, kx, kx, jnp.int32(i), n_silos, sigma_c, 0.0, 0.0,
            use_pairwise=False, use_prev=False, impl="jnp")
        total = m if total is None else total + m
    return flatbuf.unpack(layout, total, dtype=jnp.float32)
