"""Train / serve step builders: CITADEL++'s collaborative-training protocol
mapped onto the TPU mesh (DESIGN.md §2).

The clip+mask+noise math lives in ONE engine —
:class:`repro.core.dp_pipeline.DPPipeline` — and the step builders here are
mesh-placement shims around its stages:

``sync_path='fused'``   — pjit end-to-end. Per-silo grads via vmap over the
    silo axis of the batch, one ``run_central`` over the stacked packed
    buffer (aggregate corrected noise post-reduce). Supports FSDP param
    sharding. Production path.
``silo_mode='scan'``    — silo-serial fused path (100B-scale): a lax.scan
    accumulates clipped silo grads into an fsdp-sharded fp32 buffer; the
    engine's ``corrected_noise_tree`` stage runs on the accumulator.
``sync_path='barrier'`` — paper-faithful wire protocol: jax.shard_map manual
    over the silo axes (pod, data), model/TP axis left auto. Each silo emits
    the engine's ``silo_contribution`` (clip + zero-sum DP-mask + its noise
    share) and the explicit psum is the aggregation the model updater sees.

All paths produce the same aggregate: sum_i clip(g_i) + sigma*C*(xi_t -
lambda*xi_{t-1}), then update = aggregate / n_contributions via the
optimizer. Every tier runs the per-silo forward and backward pass under
``jax.named_scope("train.fwd_bwd")`` and the update under
``"train.optimizer"``, beside the engine's ``dp.*`` scopes, so a profile
splits the step's device time between the model, the optimizer and the DP
engine; the barrier tier's packed psum runs under ``dp.psum``. Every step
takes an ``active: (n_silos,) bool`` participation set (elastic silo
membership — see runtime/elastic.py); ``None`` means all silos contribute.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MeshConfig, PrivacyConfig, RunConfig
from repro.core import barrier as barrier_mod
from repro.core import dp_pipeline, flatbuf
from repro.core.dp_pipeline import DPPipeline
from repro.core.noise_correction import NoiseState, init_state as init_noise_state
from repro.distributed.sharding_rules import (constrain as constrain_logical,
                                               current_mesh, params_pspecs,
                                               spec_for)
from repro.models.registry import Model
from repro.optim.optimizers import Optimizer, make_optimizer
from repro.optim.schedules import constant, warmup_cosine


def constrain_tree(x, logical):
    return constrain_logical(x, *logical)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    noise_state: NoiseState
    step: jax.Array
    clip_bound: jax.Array  # current C_t (dynamic clipping carries it)


def effective_n_silos(run_cfg: RunConfig) -> int:
    """The silo count a step function will aggregate over. The barrier tier
    is pinned to the mesh's silo-axis extent (one silo per (pod, data) mesh
    slot — the shard_map psum runs over exactly those, so the participation
    set, noise streams and divisor must all use the same count regardless of
    ``priv.n_silos``); elsewhere an explicit ``priv.n_silos`` wins, the scan
    path defaults to the paper's 4 data owners, and the mesh extent is the
    fallback."""
    priv = run_cfg.privacy
    if priv.sync_path == "barrier" and priv.enabled:
        return run_cfg.mesh.n_silos
    if priv.n_silos:
        return priv.n_silos
    if priv.silo_mode == "scan":
        return 4  # the paper's evaluation deploys 4 data-handling silos
    return run_cfg.mesh.n_silos


def init_train_state(model: Model, run_cfg: RunConfig, key) -> TrainState:
    params = model.init(key)
    opt = make_optimizer(run_cfg.optimizer)
    return TrainState(
        params=params,
        opt_state=opt.init(params),
        noise_state=init_noise_state(jax.random.fold_in(key, 0xD0),
                                     n_silos=effective_n_silos(run_cfg)),
        step=jnp.zeros((), jnp.int32),
        clip_bound=jnp.asarray(run_cfg.privacy.clip_bound, jnp.float32),
    )


def _reshape_to_silos(batch: dict, n_silos: int) -> dict:
    out = {}
    for k, v in batch.items():
        if k == "positions" and v.ndim == 3:  # M-RoPE ids (3, B, S)
            out[k] = v.reshape((3, n_silos, v.shape[1] // n_silos) + v.shape[2:]) \
                      .transpose(1, 0, 2, 3)
        else:
            out[k] = v.reshape((n_silos, v.shape[0] // n_silos) + v.shape[1:])
    return out


# ---------------------------------------------------------------------------
# Fused path


def _active_or_full(active, pipe: DPPipeline):
    return pipe.full_active() if active is None else \
        jnp.asarray(active, jnp.bool_)


def _fused_grads(model: Model, priv: PrivacyConfig, params, batch, n_silos,
                 keys, noise_state, clip_bound, clip_key, active=None):
    """vmap shim: per-silo grads stacked as ONE (n_silos, P) packed buffer,
    then the engine's ``run_central`` does the rest — norms -> dynamic_bound
    -> clip_scale -> masked_aggregate -> corrected_noise — over the
    participation set."""
    silo_batches = _reshape_to_silos(batch, n_silos)
    layout = flatbuf.layout_of(params)  # grads share the params treedef
    pipe = DPPipeline(priv, layout, n_silos)
    active = _active_or_full(active, pipe)

    with jax.named_scope("train.fwd_bwd"):
        losses, grads = jax.vmap(jax.value_and_grad(model.loss),
                                 in_axes=(None, 0))(params, silo_batches)
    # packed outside the vmap (batched, pack's dynamic_update_slice writes
    # turn into a scatter) and behind a barrier: with the gradient program
    # fused into those writes the TPU compile takes ~20x longer (200 s
    # against 10 s for two qwen2.5-3b layers)
    g_packed = flatbuf.pack(layout, jax.lax.optimization_barrier(grads))
    # norms off the packed buffer (padding is exactly zero): one reduce
    # instead of a per-leaf sumsq chain
    norms = pipe.norms(g_packed)

    if priv.enabled and pipe.policy.mode == "perleaf":
        # legacy per-leaf noise family (force_impl / REPRO_KERNEL_IMPL):
        # aggregate packed, then the tree-level noise stage
        bound = pipe.dynamic_bound(norms, active, clip_key, clip_bound)
        g_sum = pipe.masked_aggregate(g_packed,
                                      pipe.clip_scales(norms, bound, active))
        g_tree = flatbuf.unpack(layout, g_sum, dtype=jnp.float32)
        noisy = pipe.corrected_noise_tree(g_tree, keys, noise_state, bound,
                                          active)
        new_ns = pipe.advance_state(keys, noise_state, active)
    else:
        noisy, new_ns, bound = pipe.run_central(
            g_packed, norms, keys, noise_state, clip_bound, clip_key, active)
    gates = active.astype(losses.dtype)
    loss = jnp.sum(losses * gates) / pipe.active_count(active)
    return noisy, loss, norms, new_ns, bound


def _fused_grads_scan(model: Model, priv: PrivacyConfig, params, batch,
                      n_silos, keys, noise_state, clip_bound, clip_key,
                      active=None):
    """scan shim (100B-scale): silos are processed sequentially; each silo's
    gradient is data-parallel over the whole mesh (FSDP reduce-scatter keeps
    the transient at P/n_devices), weighted by the engine's clip scale for
    the carried bound C_t (dynamic clipping is stale-by-one — the standard
    production DP-SGD quantile scheme) and its participation gate, and
    accumulated into a single fsdp-sharded fp32 buffer. The engine's
    ``corrected_noise_tree`` stage runs on the accumulator — per-leaf policy
    by default, which keeps the FSDP sharding (the packed engine would
    gather the full parameter buffer onto every device;
    ``REPRO_KERNEL_IMPL=dp_noise_tree=packed`` overrides if wanted)."""
    silo_batches = _reshape_to_silos(batch, n_silos)
    # inner batch dim stays sharded over the silo axes (the scan consumes dim0)
    silo_batches = {
        k: (constrain_tree(v, (None, None, "batch", None)) if k == "positions"
            else constrain_tree(v, (None, "batch") + (None,) * (v.ndim - 2)))
        for k, v in silo_batches.items()}

    pipe = DPPipeline(priv, flatbuf.layout_of(params), n_silos,
                      policy="perleaf")
    active = _active_or_full(active, pipe)
    param_pspecs = params_pspecs(params)

    def constrain_acc(t):
        def one(x, s):
            if all(e is None for e in s):
                return x
            return jax.lax.with_sharding_constraint(x, s)
        return jax.tree.map(one, t, param_pspecs,
                            is_leaf=lambda n: hasattr(n, "shape"))

    acc0 = constrain_acc(jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params))

    def body(carry, xs):
        acc, loss_acc = carry
        b, gate = xs
        with jax.named_scope("train.fwd_bwd"):
            loss, g = jax.value_and_grad(model.loss)(params, b)
        norm = pipe.norm_tree(g)
        scale = pipe.clip_scale(norm, clip_bound) \
            if priv.enabled else jnp.asarray(1.0, jnp.float32)
        scale = scale * gate
        acc = constrain_acc(jax.tree.map(
            lambda a, gg: a + scale * gg.astype(jnp.float32), acc, g))
        return (acc, loss_acc + loss * gate), norm

    gates = active.astype(jnp.float32)
    (g_sum, loss_sum), norms = jax.lax.scan(
        body, (acc0, jnp.zeros((), jnp.float32)), (silo_batches, gates))

    new_bound = pipe.dynamic_bound(norms, active, clip_key, clip_bound)

    if priv.enabled:
        noisy = pipe.corrected_noise_tree(g_sum, keys, noise_state,
                                          clip_bound, active)
        new_ns = pipe.advance_state(keys, noise_state, active)
    else:
        noisy, new_ns = g_sum, noise_state
    return noisy, loss_sum / pipe.active_count(active), norms, new_ns, new_bound


# ---------------------------------------------------------------------------
# Barrier path (paper-faithful)


def _barrier_grads(model: Model, priv: PrivacyConfig, mesh_cfg: MeshConfig,
                   params, batch, keys, noise_state, clip_bound, clip_key,
                   abstract_mesh, active=None):
    """shard_map shim: each silo emits the engine's ``silo_contribution``
    (clip + zero-sum mask over the active ring + its noise share, one fused
    dispatch on the packed buffer) and the explicit psum over the silo axes
    — one collective on the packed buffer — is the aggregation the model
    updater sees. The masked per-silo gradients exist on the wire exactly as
    in the paper."""
    n_silos = mesh_cfg.n_silos
    silo_axes = mesh_cfg.silo_axes
    pipe = DPPipeline(priv, flatbuf.layout_of(params), n_silos)
    if pipe.policy.mode == "perleaf":
        # the per-leaf mask family only supports the full static ring
        active = None
    active_arr = _active_or_full(active, pipe)
    has_prev_active = noise_state.prev_active is not None
    prev_active_arr = noise_state.prev_active if has_prev_active \
        else pipe.full_active()

    def silo_fn(params, batch_local, key_r, key_xi, prev_key, has_prev,
                prev_active, clip_bound, clip_key, active):
        idx = jnp.zeros((), jnp.int32)
        mult = 1
        for ax in reversed(silo_axes):
            idx = idx + jax.lax.axis_index(ax) * mult
            mult *= jax.lax.axis_size(ax)
        with jax.named_scope("train.fwd_bwd"):
            loss, g = jax.value_and_grad(model.loss)(params, batch_local)
        norm = pipe.norm_tree(g)

        if priv.dynamic_clip:
            all_norms = jax.lax.all_gather(norm[None], silo_axes)  # (n_silos, 1)
            clip_bound = pipe.dynamic_bound(all_norms.reshape(-1), active,
                                            clip_key, clip_bound)

        # clip folds into the fused packed clip+mask+noise dispatch
        scale = pipe.clip_scale(norm, clip_bound)
        keys_t = barrier_mod.BarrierKeys(key_r, key_xi, clip_key)
        ns = NoiseState(prev_key=prev_key, has_prev=has_prev,
                        prev_active=prev_active if has_prev_active else None)
        contrib = pipe.silo_contribution(g, idx, scale, active, keys_t, ns,
                                         clip_bound)
        with jax.named_scope("dp.psum"):
            summed = jax.lax.psum(contrib, silo_axes)
        agg = pipe.finalize(summed)
        gate = active[idx].astype(jnp.float32)
        loss_mean = jax.lax.psum(loss * gate, silo_axes) / \
            pipe.active_count(active)
        return agg, loss_mean, norm[None], clip_bound

    batch_spec = {k: (P(None, silo_axes) if k == "positions" and v.ndim == 3
                      else P(silo_axes))
                  for k, v in batch.items()}

    fn = jax.shard_map(
        silo_fn,
        mesh=abstract_mesh,
        in_specs=(P(), batch_spec, P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P(), P(silo_axes), P()),
        axis_names=set(silo_axes),
        check_vma=False,
    )
    agg, loss, norms, new_bound = fn(
        params, batch, keys.key_r, keys.key_xi, noise_state.prev_key,
        noise_state.has_prev, prev_active_arr, clip_bound, keys.key_clip,
        active_arr)
    new_ns = pipe.advance_state(keys, noise_state, active_arr) \
        if priv.enabled else noise_state
    return agg, loss, norms, new_ns, new_bound


# ---------------------------------------------------------------------------
# Step builders


def build_train_step(model: Model, run_cfg: RunConfig, abstract_mesh=None,
                     lr_schedule=None, elastic: bool = False):
    """The jitted CITADEL++ train step. ``train_step(state, batch, root_key,
    active=None)``: ``active`` is the (n_silos,) bool participation set for
    this step — dropped silos contribute neither gradient, mask, noise share
    nor divisor weight. ``elastic=True`` only validates up front that the
    configured tier can honour a partial set (the barrier tier needs the
    packed mask family for the active-ring construction)."""
    priv = run_cfg.privacy
    mesh_cfg = run_cfg.mesh
    opt = make_optimizer(run_cfg.optimizer)
    lr_schedule = lr_schedule or constant(run_cfg.optimizer.lr)
    n_silos = effective_n_silos(run_cfg)

    if elastic and priv.enabled and priv.sync_path == "barrier":
        policy = dp_pipeline.resolve_policy("packed", 1)
        if policy.mode == "perleaf":
            raise ValueError(
                "elastic membership on the barrier tier needs the packed "
                "mask engine (the per-leaf family only builds the full "
                "static ring); lift the dp_noise_tree=perleaf override")

    def train_step(state: TrainState, batch, root_key, active=None):
        keys = barrier_mod.step_keys(root_key, state.step)
        if active is None:
            active = jnp.ones((n_silos,), jnp.bool_)
        if active.shape != (n_silos,):
            raise ValueError(
                f"participation set has shape {active.shape}, but this step "
                f"aggregates over {n_silos} silos"
                + (" (the barrier tier pins the count to the mesh's silo-"
                   "axis extent, not priv.n_silos)"
                   if priv.sync_path == "barrier" and priv.enabled else ""))
        if priv.sync_path == "barrier" and priv.enabled:
            noisy, loss, norms, new_ns, bound = _barrier_grads(
                model, priv, mesh_cfg, state.params, batch, keys,
                state.noise_state, state.clip_bound, keys.key_clip,
                abstract_mesh, active=active)
        elif priv.silo_mode == "scan":
            noisy, loss, norms, new_ns, bound = _fused_grads_scan(
                model, priv, state.params, batch, n_silos, keys,
                state.noise_state, state.clip_bound, keys.key_clip,
                active=active)
        else:
            noisy, loss, norms, new_ns, bound = _fused_grads(
                model, priv, state.params, batch, n_silos, keys,
                state.noise_state, state.clip_bound, keys.key_clip,
                active=active)

        # the aggregate is divided by the silos that actually contributed
        n_contrib = jnp.maximum(jnp.sum(active.astype(jnp.float32)), 1.0)
        with jax.named_scope("train.optimizer"):
            grad = jax.tree.map(lambda g: g / n_contrib, noisy)
            lr = lr_schedule(state.step)
            new_params, new_opt = opt.update(state.params, state.opt_state,
                                             grad, lr)
        gates = active.astype(jnp.float32)
        norm_mean = jnp.sum(norms.reshape(-1)[:n_silos] * gates) / n_contrib \
            if norms.shape[0] == n_silos else jnp.mean(norms)
        metrics = {"loss": loss, "grad_norm_mean": norm_mean,
                   "clip_bound": bound, "lr": lr,
                   "n_contributions": n_contrib}
        return TrainState(new_params, new_opt, new_ns, state.step + 1, bound), metrics

    return train_step


def build_serve_step(model: Model, kind: str = "decode"):
    if kind == "prefill":
        def prefill_step(params, batch, cache):
            return model.prefill(params, batch, cache)
        return prefill_step

    def decode_step(params, batch, cache):
        return model.decode_step(params, batch, cache)
    return decode_step


# ---------------------------------------------------------------------------
# Sharding helpers for jit


def state_pspecs(state: TrainState):
    """PartitionSpecs for a TrainState under the current mesh context."""
    p_specs = params_pspecs(state.params)
    # opt entries mirror params: master/m/v share the params' sharding
    def opt_spec(d):
        out = {}
        for k, v in d.items():
            if k in ("master", "m", "v", "mu"):
                out[k] = p_specs
            else:
                out[k] = jax.tree.map(lambda _: P(), v)
        return out
    return TrainState(
        params=p_specs,
        opt_state=opt_spec(state.opt_state),
        noise_state=jax.tree.map(lambda _: P(), state.noise_state),
        step=P(),
        clip_bound=P(),
    )


def batch_pspec(batch, silo_axes=("pod", "data")):
    """Shard the batch dim over the silo axes where divisible; batch=1 shapes
    (long-context decode) fall back to sequence sharding / replication."""
    mesh = current_mesh()
    n = 1
    axes = tuple(a for a in silo_axes
                 if mesh is not None and a in (mesh.axis_names or ()))
    for a in axes:
        n *= mesh.shape[a]
    axes = axes or silo_axes

    def one(k, v):
        if k == "positions" and v.ndim == 3:
            if v.shape[1] % max(n, 1) == 0 and v.shape[1] > 1:
                return P(None, axes)
            return P()
        if v.shape[0] % max(n, 1) == 0 and v.shape[0] > 1:
            return P(axes)
        if v.ndim > 1 and v.shape[1] % max(n, 1) == 0 and v.shape[1] > 1:
            return P(None, axes)  # sequence-sharded fallback
        return P()

    return {k: one(k, v) for k, v in batch.items()}
