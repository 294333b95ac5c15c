"""The single DP-aggregation engine behind every execution tier.

CITADEL++'s core guarantee — the model updater only ever sees

    sum_i clip(g_i) + sigma*C*(xi_t - lambda*xi_{t-1})

— used to be implemented once per execution tier (vmap-fused, silo-serial
scan, shard_map barrier, and the TEE wire protocol), each copy re-deciding
packed-vs-perleaf and re-deriving streams. :class:`DPPipeline` is the one
engine all four tiers now build on. It is constructed once per step function
from a :class:`~repro.configs.base.PrivacyConfig` + a
:class:`~repro.core.flatbuf.PackedLayout` and exposes the stage graph

    norms -> dynamic_bound -> clip_scale -> masked_aggregate -> corrected_noise

with two cross-cutting decisions made exactly once:

* **Execution policy** (``packed`` | ``perleaf``, inner kernel impl): resolved
  through the kernel-dispatch REGISTRY at construction (honouring
  ``force_impl`` / ``REPRO_KERNEL_IMPL`` on ``dp_noise_tree``). One policy
  governs both the mask and the noise construction — all silos of a session
  must draw from the same stream family, so the old per-stage resolution was
  a correctness hazard, not a feature.
* **Participation set**: every stage takes ``active``, an ``(n_silos,)`` bool
  mask of the silos actually contributing this step. Zero-sum masks are
  generated over the ring of *active* silos (``next_active`` skips dropped
  members, so the r-terms still telescope to zero for any k <= n), each
  active silo's fresh-noise share is ``sigma_c/sqrt(k)`` (aggregate noise std
  stays exactly ``sigma_c`` for any k), and the aggregate is divided by the
  actual contribution count — elastic membership without touching the
  guarantee.

Three mask constructions run through the same stages: ``pairwise`` (the
key-derived zero-sum ring above), ``admin`` (the paper-faithful O(n*P) mask
set the admin generates centrally — dropped silos get zero rows, the last
active silo closes the sum to xi, and the -lam*xi_{t-1} correction rides in
the closing row since the admin owns every stream), and ``none``
(confidentiality-only clipped sync).

Noise-correction under elasticity: the lambda-corrected term
``-lam*xi_{t-1}`` is carried *per silo*. :class:`NoiseState` remembers the
previous step's participation set; at step t, silo i subtracts its own share
of xi_{t-1} (std ``sigma_c/sqrt(k_{t-1})``) only if it contributed at t-1 and
is active now. A silo that drops out takes its correction share with it: the
uncorrected remainder of xi_{t-1} simply persists in the model. That only
*adds* residual noise, so the accountant's epsilon (computed for the fully
corrected mechanism) remains a valid upper bound.

Each stage runs under a ``jax.named_scope`` (``dp.norms``, ``dp.clip``,
``dp.mask``, ``dp.noise``; ``dp.pack`` and ``dp.unpack`` on the flat-buffer
conversions), so a profile attributes the engine's device time stage by
stage.

Tier placement stays in the callers: ``distributed/steps.py`` wraps these
stages in vmap / scan / shard_map, ``core/tee/components.py`` invokes them
per protocol message. Neither re-implements any of the math.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import PrivacyConfig
from repro.core import clipping, flatbuf, masking, noise_correction
from repro.core.barrier import BarrierKeys, dynamic_bound_from_percentiles
from repro.core.flatbuf import PackedLayout, scoped
from repro.core.noise_correction import NoiseState
from repro.kernels.dispatch import REGISTRY
from repro.kernels.dp_clip import ops as clip_ops
from repro.kernels.dp_fused import ops as fused_ops

NOISE_TREE = "dp_noise_tree"


@jax.jit
def _silo_stream(key, silo, idx):
    """One silo's standard-normal stream over global packed indices — the
    SAME counter construction the fused clip_mask graph draws in-graph.

    This is the single shared jit behind every externally drawn xi/xp (the
    wire tier's speculative rounds): a stream cached from round t and one
    recomputed from the carried prev_key at t+1 are outputs of the same
    compiled function on the same inputs, so stream reuse is bitwise
    equal to recomputation BY CONSTRUCTION — no cross-graph FMA-contraction
    exposure (two different jitted graphs of the same formula may disagree
    by 1 ulp; one graph cannot disagree with itself)."""
    from repro.kernels.dp_fused.ref import _stream
    return _stream(key, idx, silo)


def is_static_full(active) -> bool:
    """True iff the participation set is *statically* known to be all-active
    (``None``, or a concrete all-True array at trace time). The engine then
    emits the fixed-membership graph: no ring-neighbour argmax, no per-silo
    gate multiplies, constant stream scales. Every elided op is a
    multiply-by-1.0 or a reduction over a constant, so the fast path is
    bit-identical to the dynamic graph evaluated on an all-active set."""
    if active is None:
        return True
    if isinstance(active, jax.core.Tracer):
        return False
    return bool(np.all(np.asarray(active)))


def _static_all_true(vec) -> bool:
    """Concrete all-True vector (used for the carried prev_active set)."""
    return vec is not None and not isinstance(vec, jax.core.Tracer) \
        and bool(np.all(np.asarray(vec)))


@dataclass(frozen=True)
class ExecutionPolicy:
    """How the pipeline executes: ``packed`` runs every stage on the flat
    buffer through the fused kernels (``inner`` picks pallas/jnp/auto for the
    tensor-level dispatch); ``perleaf`` keeps the legacy per-leaf jax.random
    construction (load-bearing for FSDP-sharded accumulators, where packing
    would gather the full parameter buffer onto every device)."""

    mode: str   # 'packed' | 'perleaf'
    inner: str  # tensor-kernel impl under packed: 'auto' | 'pallas' | 'jnp'


def resolve_policy(request: str, n_leaves: int) -> ExecutionPolicy:
    """Resolve the execution policy through the registry — exactly once per
    pipeline. ``force_impl(...)`` / ``REPRO_KERNEL_IMPL=dp_noise_tree=...``
    override ``request`` as usual; legacy impl names map onto the two modes
    (pallas -> packed/pallas, jnp -> perleaf)."""
    name = REGISTRY.resolve(NOISE_TREE, request, {"n_leaves": n_leaves}).name
    if name in ("perleaf", "jnp"):
        return ExecutionPolicy("perleaf", "jnp")
    return ExecutionPolicy("packed", "pallas" if name == "pallas" else "auto")


class DPPipeline:
    """One guarded aggregation engine, four mesh placements (DESIGN.md §2)."""

    def __init__(self, priv: PrivacyConfig, layout: PackedLayout,
                 n_silos: int, policy: str = "packed"):
        if priv.mask_mode not in ("pairwise", "admin", "none"):
            raise ValueError(
                f"DPPipeline supports mask_mode pairwise|admin|none, got "
                f"{priv.mask_mode!r}")
        self.priv = priv
        self.layout = layout
        self.n_silos = int(n_silos)
        self.policy = resolve_policy(policy, layout.n_leaves)

    # -- participation set --------------------------------------------------
    def full_active(self) -> jax.Array:
        return jnp.ones((self.n_silos,), jnp.bool_)

    def active_count(self, active) -> jax.Array:
        """Number of contributing silos (>=1), the aggregate's divisor."""
        if is_static_full(active):
            return jnp.asarray(float(self.n_silos), jnp.float32)
        return jnp.maximum(jnp.sum(active.astype(jnp.float32)), 1.0)

    def next_active(self, silo, active) -> jax.Array:
        """The next *active* silo after ``silo`` in the ring — the pairwise
        mask neighbour. Skipping dropped members keeps the r-terms
        telescoping to zero over any participation set; a statically full
        set short-circuits to the fixed ring (no argmax/gather)."""
        if is_static_full(active):
            return (jnp.asarray(silo, jnp.int32) + 1) % self.n_silos
        offs = jnp.arange(1, self.n_silos + 1, dtype=jnp.int32)
        cand = (jnp.asarray(silo, jnp.int32) + offs) % self.n_silos
        return cand[jnp.argmax(active[cand])]

    def prev_active(self, state: NoiseState) -> jax.Array:
        pa = getattr(state, "prev_active", None)
        if pa is None or pa.shape != (self.n_silos,):
            return self.full_active()  # legacy state: all silos contributed
        return pa

    @scoped("dp.noise")
    def advance_state(self, keys: BarrierKeys, state: NoiseState,
                      active) -> NoiseState:
        """The state every tier carries to step t+1: the 32-byte key that
        generated xi_t plus the participation set it was drawn over. Keeps
        the incoming structure (legacy 2-field states stay 2-field)."""
        pa = None if getattr(state, "prev_active", None) is None else active
        return NoiseState(prev_key=masking._raw(keys.key_xi),
                          has_prev=jnp.ones((), jnp.bool_), prev_active=pa)

    # -- per-stream noise scales --------------------------------------------
    def _stream_scales(self, bound, active, state: NoiseState):
        """(s_t, s_prev, prev_active): per-silo noise stds at steps t / t-1.
        k active streams at sigma_c/sqrt(k) sum to std exactly sigma_c.
        Concrete participation sets resolve to constant scales (the sqrt of
        a constant folds at compile time — same fp32 value either way)."""
        sc = self.priv.sigma * jnp.asarray(bound, jnp.float32)
        s = sc / jnp.sqrt(self.active_count(active))
        pa = self.prev_active(state)
        if isinstance(pa, jax.core.Tracer):
            k_prev = jnp.maximum(jnp.sum(pa.astype(jnp.float32)), 1.0)
        else:
            k_prev = jnp.asarray(max(float(np.sum(np.asarray(pa))), 1.0),
                                 jnp.float32)
        return s, sc / jnp.sqrt(k_prev), pa

    # -- admin mask construction (paper-faithful O(n*P) baseline) ------------
    def _admin_correction(self, template, state: NoiseState, bound):
        """The admin-owned ``lam*xi_{t-1}`` tree (regenerated from the
        carried 32-byte key), or None when correction is off/unprimed."""
        if not self.priv.noise_lambda > 0.0:
            return None
        sigma_c = self.priv.sigma * jnp.asarray(bound, jnp.float32)
        hp = jnp.where(state.has_prev, 1.0, 0.0)
        lam = self.priv.noise_lambda * hp
        prev = masking.admin_xi(jax.random.wrap_key_data(state.prev_key),
                                template, sigma_c)
        return jax.tree.map(lambda x: lam * x, prev)

    def _admin_mask_set(self, template, active, keys: BarrierKeys,
                        state: NoiseState, bound):
        """The stacked (n_silos, ...) mask trees for one step: zero rows for
        dropped silos, active rows telescoping to xi_t - lam*xi_{t-1}.
        ``template`` supplies leaf shapes only (values unread)."""
        sigma_c = self.priv.sigma * jnp.asarray(bound, jnp.float32)
        return masking.admin_masks(
            jax.random.wrap_key_data(masking._raw(keys.key_xi)), template,
            self.n_silos, sigma_c, self.priv.mask_scale * sigma_c,
            active=active,
            correction=self._admin_correction(template, state, bound))

    @scoped("dp.noise")
    def admin_noise_tree(self, g_sum_tree, keys: BarrierKeys,
                         state: NoiseState, bound):
        """Central-tier aggregate noise under admin masks: regenerate the
        exact xi_t (and correction) the distributed mask set telescopes to,
        so the fused/scan tiers reproduce the wire baseline's aggregate."""
        sigma_c = self.priv.sigma * jnp.asarray(bound, jnp.float32)
        xi = masking.admin_xi(
            jax.random.wrap_key_data(masking._raw(keys.key_xi)),
            g_sum_tree, sigma_c)
        corr = self._admin_correction(g_sum_tree, state, bound)
        if corr is not None:
            xi = jax.tree.map(lambda a, c: a - c, xi, corr)
        return jax.tree.map(
            lambda g, n: (g.astype(jnp.float32) + n).astype(g.dtype),
            g_sum_tree, xi)

    # -- stage: norms --------------------------------------------------------
    @scoped("dp.norms")
    def norms(self, stacked) -> jax.Array:
        """Per-silo global norms off a stacked (n, P) packed buffer (padding
        is exactly zero, so one reduce replaces the per-leaf sumsq chain)."""
        g32 = stacked.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(g32 * g32, axis=-1))

    @scoped("dp.norms")
    def norm_tree(self, tree) -> jax.Array:
        return clipping.global_norm(tree)

    # -- stage: dynamic_bound ------------------------------------------------
    @scoped("dp.clip")
    def dynamic_bound(self, norms, active, clip_key, fallback) -> jax.Array:
        """§4.3 percentile protocol over the *active* silos' norms; returns
        ``fallback`` (the carried bound) when dynamic clipping is off."""
        if not (self.priv.enabled and self.priv.dynamic_clip):
            return jnp.asarray(fallback, jnp.float32)
        pcts = clipping.local_percentiles(norms, mask=active)
        return dynamic_bound_from_percentiles(pcts[None], self.priv, clip_key)

    # -- stage: clip_scale ---------------------------------------------------
    @scoped("dp.clip")
    def clip_scale(self, norm, bound) -> jax.Array:
        return clipping.clip_scale(norm, bound)

    @scoped("dp.clip")
    def clip_scales(self, norms, bound, active) -> jax.Array:
        """DP-SGD clip factors, zeroed for dropped silos — the single place
        deciding who contributes what weight to the aggregate."""
        scales = clipping.clip_scale(norms, bound) if self.priv.enabled \
            else jnp.ones_like(norms, jnp.float32)
        if is_static_full(active):
            return scales  # gating is a multiply-by-ones: skip it
        return scales * active.astype(scales.dtype)

    # -- stage: masked_aggregate ---------------------------------------------
    @scoped("dp.mask")
    def masked_aggregate(self, stacked, scales) -> jax.Array:
        """sum_i scales_i * g_i over a stacked (n, P) buffer — one registry
        dispatch. Central tiers elide the zero-sum masks (they cancel in the
        aggregate by construction); the per-silo view of this stage is
        :meth:`silo_contribution`."""
        impl = self.policy.inner if self.policy.mode == "packed" else "auto"
        return clip_ops.clipped_sum(stacked, scales, impl=impl)

    @scoped("dp.mask")
    def admin_closing_row(self, template, active, keys: BarrierKeys,
                          state: NoiseState, bound):
        """Admin-side construction of the closing silo's mask row — the one
        O(k*P) row in the admin mask set. Returns ``(closing, row)``.

        At n silos, letting every handler rebuild its own row keeps n-1 of
        them at O(P) but the *closing* handler at O(k*P); the admin (who owns
        every stream anyway) computes that row once per round and ships it
        with the step keys, so per-handler work is O(P) at any n. The row is
        produced by the IDENTICAL ``masking.admin_mask_row`` call (same
        streams, same sequential-subtraction fp association), so a handler
        using the distributed row is bit-identical to one rebuilding it."""
        act = np.asarray(active).astype(bool)
        closing = int(self.n_silos - 1 - np.argmax(act[::-1]))
        sigma_c = self.priv.sigma * jnp.asarray(bound, jnp.float32)
        row = masking.admin_mask_row(
            jax.random.wrap_key_data(masking._raw(keys.key_xi)), template,
            self.n_silos, closing, sigma_c, self.priv.mask_scale * sigma_c,
            active=act,
            correction=self._admin_correction(template, state, bound))
        return closing, row

    @scoped("dp.noise")
    def noise_stream(self, key, silo) -> jax.Array:
        """This silo's (P,) standard-normal stream for a 32-byte step key —
        the exact values the fused graph would draw in-graph for xi (step
        key) or xi_prev (carried prev_key). Drawn through one shared
        standalone jit so the wire tier's speculative stream cache is
        bitwise-equal to an inline recompute (see ``_silo_stream``)."""
        idx = jnp.arange(self.layout.total, dtype=jnp.uint32)
        return _silo_stream(jnp.asarray(key), jnp.asarray(silo, jnp.int32),
                            idx)

    @scoped("dp.mask")
    def silo_contribution(self, g_tree, silo, scale, active, keys: BarrierKeys,
                          state: NoiseState, bound, admin_row=None,
                          xi=None, xp=None):
        """One silo's wire contribution: clip + zero-sum mask over the active
        ring + its sigma_c/sqrt(k) noise share + its lambda-correction share,
        in one fused dispatch. Summing the active silos' outputs (psum on the
        barrier tier, updater-side reduce on the wire tier) yields exactly
        ``sum_i clip(g_i) + sigma*C*(xi_t - lam*xi_{t-1})``.

        ``admin_row``: admin-distributed mask row for THIS silo (admin mode
        only; see :meth:`admin_closing_row`) — used instead of regenerating
        the row locally.

        ``xi``/``xp``: externally drawn noise streams (packed pairwise mode
        only — the speculative wire tier draws them via :meth:`noise_stream`
        and reuses its round-t xi as round-(t+1)'s xi_prev, since the admin
        carries exactly that key forward). ``None`` draws in-graph.

        Returns a packed (P,) buffer under the packed policy (psum it, then
        :meth:`finalize`), a pytree under perleaf (which supports the full
        ring only — elastic runs require the packed policy)."""
        priv = self.priv
        silo = jnp.asarray(silo, jnp.int32)
        static = is_static_full(active)
        gate = 1.0 if static else active[silo].astype(jnp.float32)
        sigma_c = priv.sigma * jnp.asarray(bound, jnp.float32)
        use_prev = priv.noise_lambda > 0.0
        if (xi is not None or xp is not None) and (
                priv.mask_mode != "pairwise"
                or self.policy.mode != "packed"):
            raise ValueError(
                "external xi/xp streams only apply to the packed pairwise "
                "construction (admin/none/perleaf draw their own)")
        if priv.mask_mode == "none":
            # confidentiality-only sync: clipped gradient, no DP terms
            scaled = scale * gate
            return jax.tree.map(
                lambda x: (x.astype(jnp.float32) * scaled).astype(x.dtype),
                g_tree)
        if priv.mask_mode == "admin":
            # paper-faithful O(n*P) construction through the same stage:
            # rows of dropped silos are zero, the last active silo closes
            # the sum to xi, and the -lam*xi_{t-1} correction rides in the
            # closing row — the admin owns every stream, so there are no
            # per-silo shares to carry. With a concrete silo/active (the
            # wire tier: one handler per message) each silo fetches only its
            # own row, keeping the per-step total at the paper's O(n*P);
            # traced callers (shard_map) fall back to the stacked set.
            scaled = scale * gate
            concrete = not (isinstance(silo, jax.core.Tracer)
                            or isinstance(active, jax.core.Tracer))
            if concrete:
                sigma_c_a = priv.sigma * jnp.asarray(bound, jnp.float32)
                act_np = np.asarray(active).astype(bool)
                closing = int(self.n_silos - 1 - np.argmax(act_np[::-1]))
                if admin_row is not None and int(silo) == closing:
                    # admin-distributed closing row (O(P) fan-out at any n)
                    row = admin_row
                else:
                    # only the closing row carries the correction; skip the
                    # O(P) xi_{t-1} regeneration for every other handler
                    corr = self._admin_correction(g_tree, state, bound) \
                        if int(silo) == closing else None
                    row = masking.admin_mask_row(
                        jax.random.wrap_key_data(masking._raw(keys.key_xi)),
                        g_tree, self.n_silos, int(silo), sigma_c_a,
                        priv.mask_scale * sigma_c_a, active=active,
                        correction=corr)
                return jax.tree.map(
                    lambda x, m: x.astype(jnp.float32) * scaled + m * gate,
                    g_tree, row)
            masks = self._admin_mask_set(g_tree, active, keys, state, bound)
            return jax.tree.map(
                lambda x, m: x.astype(jnp.float32) * scaled + m[silo] * gate,
                g_tree, masks)
        s, s_prev, pa = self._stream_scales(bound, active, state)
        hp = jnp.where(state.has_prev, 1.0, 0.0)
        pa_gate = 1.0 if _static_all_true(pa) \
            else pa[silo].astype(jnp.float32)
        lam_gate = priv.noise_lambda * hp * gate * pa_gate
        if self.policy.mode == "perleaf":
            # legacy per-leaf stream family; the ring is static (full), so a
            # partial participation set would leave uncancelled +-B*r terms
            # in the aggregate. build_train_step rejects elastic barrier
            # runs up front; the wire tier passes concrete masks, caught here
            if not isinstance(active, jax.core.Tracer) \
                    and not bool(jnp.all(active)):
                raise ValueError(
                    "the per-leaf mask family only builds the full static "
                    "ring; dropping silos needs the packed policy (lift the "
                    "dp_noise_tree=perleaf override for elastic runs)")
            scaled = jax.tree.map(
                lambda x: (x.astype(jnp.float32) * scale).astype(x.dtype),
                g_tree)
            masked = masking.pairwise_mask_tree(
                scaled, keys.key_r, keys.key_xi, silo, self.n_silos,
                sigma_c, priv.mask_scale * sigma_c, impl="perleaf")
            if use_prev:
                prev = masking.pairwise_mask_only(
                    g_tree, keys.key_r, state.prev_key, silo, self.n_silos,
                    sigma_c, 0.0, impl="perleaf")
                masked = jax.tree.map(
                    lambda m, p: m - lam_gate * p.astype(m.dtype), masked, prev)
            return masked
        # the barrier keeps pack's writes out of the kernel's operand fusion:
        # fused, the TPU compile of the barrier tier's step takes ~6x
        # longer (65 s against 11 s for two qwen2.5-3b layers on 4 chips)
        packed = jax.lax.optimization_barrier(
            flatbuf.pack(self.layout, g_tree))
        return fused_ops.clip_mask_packed(
            packed, scale if static else scale * gate,
            masking._raw(keys.key_r),
            masking._raw(keys.key_xi), state.prev_key, silo, self.n_silos,
            sigma_c, priv.mask_scale * sigma_c * gate, lam_gate,
            use_pairwise=True, use_prev=use_prev, impl=self.policy.inner,
            nxt=self.next_active(silo, active),
            noise_scale=s if static else s * gate,
            prev_noise_scale=s_prev, xi=xi, xp=xp)

    @scoped("dp.unpack")
    def finalize(self, agg):
        """Aggregated contribution -> fp32 gradient pytree (unpacks packed
        buffers; perleaf aggregates are already trees)."""
        if isinstance(agg, jax.Array) and agg.ndim == 1:
            return flatbuf.unpack(self.layout, agg, dtype=jnp.float32)
        return jax.tree.map(lambda x: x.astype(jnp.float32), agg)

    # -- stage: corrected_noise ----------------------------------------------
    @scoped("dp.noise")
    def corrected_noise_packed(self, g_sum, keys: BarrierKeys,
                               state: NoiseState, bound, active) -> jax.Array:
        """Post-reduce corrected DP noise on a packed (P,) aggregate: the
        *same* per-silo streams the barrier/wire tiers emit, accumulated
        sequentially in silo order (bit-identical to the wire updater's
        reduce). Dropped silos contribute no fresh noise; the correction
        share of silo i applies iff it was active at t-1 and is active now.

        All n streams are generated by ONE ``noise_batch`` dispatch (the
        per-silo gates ride in as (n,) scale vectors — products of {0,1}
        floats are exact, so gating-by-vector is bit-identical to the n
        separate gated launches this replaces)."""
        priv = self.priv
        s, s_prev, pa = self._stream_scales(bound, active, state)
        kx = masking._raw(keys.key_xi)
        hp = jnp.where(state.has_prev, 1.0, 0.0)
        use_prev = priv.noise_lambda > 0.0
        static = is_static_full(active)
        pa_full = _static_all_true(pa)
        ones = jnp.ones((self.n_silos,), jnp.float32)
        gates = ones if static else active.astype(jnp.float32)
        pa_gates = ones if pa_full else \
            jnp.asarray(pa).astype(jnp.float32)
        noise_scales = s * gates
        lam_gates = priv.noise_lambda * hp * gates * pa_gates
        return fused_ops.noise_batch_packed(
            g_sum, kx, state.prev_key, noise_scales, lam_gates, s_prev,
            use_prev=use_prev, impl=self.policy.inner)

    @scoped("dp.noise")
    def corrected_noise_tree(self, g_sum_tree, keys: BarrierKeys,
                             state: NoiseState, bound, active):
        """Tree-level corrected noise for the central tiers. Packed policy
        routes through :meth:`corrected_noise_packed`; perleaf keeps the
        sharding-preserving per-leaf jax.random construction (one stream at
        full sigma_c — the aggregate noise std is k-independent, so elastic
        participation needs no per-stream bookkeeping there). Admin mode
        regenerates the exact xi the O(n*P) mask set telescopes to."""
        if self.priv.mask_mode == "admin":
            return self.admin_noise_tree(g_sum_tree, keys, state, bound)
        if self.policy.mode == "packed":
            packed = flatbuf.pack(self.layout, g_sum_tree)
            noisy = self.corrected_noise_packed(packed, keys, state, bound,
                                                active)
            return flatbuf.unpack(self.layout, noisy, dtype=jnp.float32)
        sigma_c = self.priv.sigma * jnp.asarray(bound, jnp.float32)
        noise, _ = noise_correction.corrected_noise(
            g_sum_tree, keys.key_xi, state, sigma_c, self.priv.noise_lambda)
        return jax.tree.map(
            lambda g, n: (g.astype(jnp.float32) + n).astype(g.dtype),
            g_sum_tree, noise)

    # -- composed runs --------------------------------------------------------
    def run_central(self, g_stacked, norms, keys: BarrierKeys,
                    state: NoiseState, bound, clip_key, active):
        """The whole stage graph for a central tier holding all silo grads as
        a stacked (n, P) packed buffer (the vmap-fused tier). Returns
        (noisy fp32 tree, new_state, bound). The staged chain deliberately
        stays elementwise (no dot_general): XLA fuses it straight into the
        noise epilogue, which measures faster than the fused ``clip_sum``
        front end in the composed graph (see kernels_bench dp_pipeline
        rows)."""
        bound = self.dynamic_bound(norms, active, clip_key, bound)
        scales = self.clip_scales(norms, bound, active)
        g_sum = self.masked_aggregate(g_stacked, scales)
        if self.priv.enabled and self.priv.mask_mode == "admin":
            g_tree = flatbuf.unpack(self.layout, g_sum, dtype=jnp.float32)
            noisy_tree = self.admin_noise_tree(g_tree, keys, state, bound)
            return noisy_tree, self.advance_state(keys, state, active), bound
        if self.priv.enabled:
            noisy = self.corrected_noise_packed(g_sum, keys, state, bound,
                                                active)
            new_state = self.advance_state(keys, state, active)
        else:
            noisy, new_state = g_sum, state
        return flatbuf.unpack(self.layout, noisy, dtype=jnp.float32), \
            new_state, bound


def reduce_contributions(updates):
    """The model updater's aggregation stage: sequential sum of masked
    per-silo updates in silo order (matching the engine's noise-accumulation
    order, so wire-tier aggregates are bit-reproducible against
    :meth:`DPPipeline.corrected_noise_packed`)."""
    total = None
    for u in updates:
        total = u if total is None else jax.tree.map(
            lambda a, b: a + b.astype(a.dtype), total, u)
    return total
