"""Public API for the packed flat-buffer DP engine, routed through the
kernel-dispatch registry (three tensor-level kernels: ``dp_fused_clip_sum``,
``dp_fused_clip_mask`` and ``dp_fused_noise_batch``) plus the pack -> kernel
-> unpack tree helpers the core modules build on."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import flatbuf
from repro.kernels.dispatch import kernel_variant, on_tpu, REGISTRY
from repro.kernels.dp_fused import ref
from repro.kernels.dp_fused.dp_fused import (clip_mask_pallas,
                                             clip_sum_pallas,
                                             noise_batch_pallas)

CLIP_SUM = "dp_fused_clip_sum"
CLIP_MASK = "dp_fused_clip_mask"
NOISE_BATCH = "dp_fused_noise_batch"


def _divisible(d: int, block: int) -> bool:
    return d % min(block, d) == 0


@kernel_variant(CLIP_SUM, "pallas", priority=100,
                predicate=lambda ctx: _divisible(ctx["P"], 512),
                auto_predicate=lambda ctx: ctx["on_tpu"],
                doc="fused Pallas sumsq+scale+accumulate, one launch")
def _clip_sum_pallas(g, clip_bound):
    return clip_sum_pallas(g, clip_bound, interpret=not on_tpu())


@kernel_variant(CLIP_SUM, "jnp", priority=10, doc="jnp reference")
def _clip_sum_jnp(g, clip_bound):
    return ref.clip_sum_ref(g, clip_bound)


@kernel_variant(CLIP_MASK, "pallas", priority=100,
                predicate=lambda ctx: _divisible(ctx["P"], 1024),
                auto_predicate=lambda ctx: ctx["on_tpu"],
                doc="fused Pallas clip+mask+corrected-noise in VMEM")
def _clip_mask_pallas(g, scale, key_r, key_xi, prev_key, silo, n_silos,
                      sigma_c, b_scale, lam_gate, use_pairwise=True,
                      use_prev=True, nxt=None, noise_scale=None,
                      prev_noise_scale=None, xi=None, xp=None):
    if xi is not None or xp is not None:
        # externally drawn streams are a host-protocol feature (the wire
        # tier's speculative rounds); the TPU kernel regenerates streams in
        # VMEM precisely because that beats hauling them through HBM, so
        # injected streams route through the jnp reference instead
        return ref.clip_mask_ref(g, scale, key_r, key_xi, prev_key, silo,
                                 n_silos, sigma_c, b_scale, lam_gate,
                                 use_pairwise=use_pairwise, use_prev=use_prev,
                                 nxt=nxt, noise_scale=noise_scale,
                                 prev_noise_scale=prev_noise_scale,
                                 xi=xi, xp=xp)
    return clip_mask_pallas(g, scale, key_r, key_xi, prev_key, silo, n_silos,
                            sigma_c, b_scale, lam_gate,
                            use_pairwise=use_pairwise, use_prev=use_prev,
                            interpret=not on_tpu(), nxt=nxt,
                            noise_scale=noise_scale,
                            prev_noise_scale=prev_noise_scale)


@kernel_variant(CLIP_MASK, "jnp", priority=10,
                doc="jnp reference (bit-identical streams)")
def _clip_mask_jnp(g, scale, key_r, key_xi, prev_key, silo, n_silos, sigma_c,
                   b_scale, lam_gate, use_pairwise=True, use_prev=True,
                   nxt=None, noise_scale=None, prev_noise_scale=None,
                   xi=None, xp=None):
    return ref.clip_mask_ref(g, scale, key_r, key_xi, prev_key, silo, n_silos,
                             sigma_c, b_scale, lam_gate,
                             use_pairwise=use_pairwise, use_prev=use_prev,
                             nxt=nxt, noise_scale=noise_scale,
                             prev_noise_scale=prev_noise_scale,
                             xi=xi, xp=xp)


@kernel_variant(NOISE_BATCH, "pallas", priority=100,
                predicate=lambda ctx: _divisible(ctx["P"], 1024),
                auto_predicate=lambda ctx: ctx["on_tpu"],
                doc="all n corrected-noise streams in one VMEM launch")
def _noise_batch_pallas(g_sum, key_xi, prev_key, noise_scales, lam_gates,
                        prev_noise_scale, use_prev=True):
    return noise_batch_pallas(g_sum, key_xi, prev_key, noise_scales,
                              lam_gates, prev_noise_scale,
                              use_prev=use_prev, interpret=not on_tpu())


@kernel_variant(NOISE_BATCH, "jnp", priority=10,
                doc="jnp reference (bit-identical batched streams)")
def _noise_batch_jnp(g_sum, key_xi, prev_key, noise_scales, lam_gates,
                     prev_noise_scale, use_prev=True):
    return ref.noise_batch_ref(g_sum, key_xi, prev_key, noise_scales,
                               lam_gates, prev_noise_scale,
                               use_prev=use_prev)


def clip_sum_packed(g, clip_bound, impl: str = "auto"):
    """g: (B, P) packed per-example grads -> (clipped sum (P,), norms (B,))."""
    return REGISTRY.dispatch(CLIP_SUM, impl, {"P": g.shape[-1]},
                             g, clip_bound)


def clip_mask_packed(g, scale, key_r, key_xi, prev_key, silo, n_silos: int,
                     sigma_c, b_scale, lam_gate, use_pairwise: bool = True,
                     use_prev: bool = True, impl: str = "auto", nxt=None,
                     noise_scale=None, prev_noise_scale=None, xi=None,
                     xp=None):
    """g: packed (P,) -> fp32 clipped+masked+corrected buffer (see ref).
    ``nxt``/``noise_scale``/``prev_noise_scale`` are the elastic-membership
    overrides (ring neighbour + per-stream stds for the active counts);
    ``xi``/``xp`` inject externally drawn noise streams (speculative wire
    rounds — see ref.clip_mask_ref)."""
    return REGISTRY.dispatch(
        CLIP_MASK, impl, {"P": g.shape[-1]},
        g, scale, key_r, key_xi, prev_key, silo, n_silos, sigma_c, b_scale,
        lam_gate, use_pairwise=use_pairwise, use_prev=use_prev, nxt=nxt,
        noise_scale=noise_scale, prev_noise_scale=prev_noise_scale,
        xi=xi, xp=xp)


def noise_batch_packed(g_sum, key_xi, prev_key, noise_scales, lam_gates,
                       prev_noise_scale, use_prev: bool = True,
                       impl: str = "auto"):
    """g_sum: packed (P,) aggregate -> fp32 aggregate + all n per-silo
    corrected-noise shares, one dispatch (see ref.noise_batch_ref).
    ``noise_scales``/``lam_gates`` are per-silo (n,) vectors with the
    caller's participation gates folded in."""
    return REGISTRY.dispatch(
        NOISE_BATCH, impl, {"P": g_sum.shape[-1]},
        g_sum, key_xi, prev_key, noise_scales, lam_gates, prev_noise_scale,
        use_prev=use_prev)


# ---------------------------------------------------------------------------
# Tree-level helpers: pack once, dispatch once, unpack once


def packed_clip_and_sum(grads_tree, clip_bound, impl: str = "auto"):
    """Per-example clip over a pytree of (B, ...) grads via one packed
    (B, P) buffer. Returns (clipped-sum tree fp32, per-example norms)."""
    layout = flatbuf.layout_of(grads_tree, batch_dims=1)
    packed = flatbuf.pack(layout, grads_tree)
    summed, norms = clip_sum_packed(packed, clip_bound, impl=impl)
    return flatbuf.unpack(layout, summed, dtype=jnp.float32), norms


def packed_mask_tree(grads, key_r, key_xi, silo, n_silos: int, sigma_c,
                     b_scale, impl: str = "auto"):
    """Pairwise zero-sum mask over a whole pytree in one kernel dispatch."""
    layout = flatbuf.layout_of(grads)
    packed = flatbuf.pack(layout, grads)
    masked = clip_mask_packed(packed, 1.0, key_r, key_xi, key_xi, silo,
                              n_silos, sigma_c, b_scale, 0.0,
                              use_pairwise=True, use_prev=False, impl=impl)
    return flatbuf.unpack(layout, masked)
