"""Top-k MoE FFN with capacity-based dispatch (GShard-style) and expert
parallelism over the ``model`` mesh axis.

Dispatch is *group-local* (EXPERIMENTS.md §Perf iteration 1): tokens are
split into G groups aligned with the data-axis shards and the capacity
bookkeeping (cumsum / scatter / gather) runs per group under vmap, so none
of it crosses shards. The v1 global-cumsum dispatch all-reduced full
(E*C, D) buffers (2e12 B/chip on qwen3-moe) and, unsharded in C, ran every
silo's expert GEMMs on every data shard (9x flops).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import sharding_rules
from repro.distributed.sharding_rules import constrain
from repro.models.layers import dense_init


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32):
    kr, kg, ku, kd = jax.random.split(key, 4)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    s_in = (1.0 / d) ** 0.5
    s_out = (1.0 / f) ** 0.5
    return {
        "router": dense_init(kr, d, E, jnp.float32),  # router stays fp32
        "we_gate": (jax.random.normal(kg, (E, d, f), jnp.float32) * s_in).astype(dtype),
        "we_up": (jax.random.normal(ku, (E, d, f), jnp.float32) * s_in).astype(dtype),
        "we_down": (jax.random.normal(kd, (E, f, d), jnp.float32) * s_out).astype(dtype),
    }


def _topk_gates(logits: jax.Array, k: int):
    """Renormalized top-k gates. logits (T, E) fp32 -> gates (T,K), idx (T,K)."""
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.clip(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, idx


def _positions_in_expert(idx: jax.Array, E: int, capacity: int):
    """Slot assignment per (token, k). idx (T, K) int32 -> pos (T, K) int32,
    keep (T, K) bool. Sequential over K slots (K <= 8)."""
    T, K = idx.shape
    counts = jnp.zeros((E,), jnp.int32)
    pos = []
    keep = []
    for k in range(K):
        onehot = jax.nn.one_hot(idx[:, k], E, dtype=jnp.int32)  # (T, E)
        ranks = jnp.cumsum(onehot, axis=0) - onehot  # rank among slot-k picks
        p = jnp.sum(ranks * onehot, axis=1) + counts[idx[:, k]]
        counts = counts + jnp.sum(onehot, axis=0)
        pos.append(p)
        keep.append(p < capacity)
    return jnp.stack(pos, 1), jnp.stack(keep, 1)


def _dispatch_groups() -> int:
    """Static dispatch-group count = product of the (auto) silo axes. Groups
    align with data shards, so per-group cumsum/scatter never cross shards
    under pjit (EXPERIMENTS.md §Perf iteration 1: the global-cumsum dispatch
    all-reduced full (E*C, D) buffers, 2e12 B/chip on qwen3-moe; a nested
    shard_map formulation crashed XLA, so groups-by-construction it is)."""
    mesh = sharding_rules.current_mesh()
    if mesh is None:
        return 1
    auto = sharding_rules.auto_axis_names(mesh)
    g = 1
    for name in mesh.axis_names:
        if name in ("pod", "data") and name in auto:
            g *= mesh.shape[name]
    return max(g, 1)


def moe_apply(p, x, cfg: ModelConfig, capacity_factor: float = 1.25):
    """Top-k MoE. x: (B, S, D) -> (B, S, D), plus aux load-balance loss.

    §Perf iteration 1 history: (a) unsharded dispatch buffer -> every data
    shard ran all expert GEMMs (9x flops); fixed by sharding the buffer over
    (experts='model', capacity='data'). (b) group-local dispatch via vmap and
    via nested shard_map both failed (partitioner drops vmapped constraints;
    shard_map+scatter crashes XLA) — the remaining scatter-add reduction is
    the known XLA-partitioner limitation; a TPU deployment replaces it with
    an all-to-all dispatch kernel (see EXPERIMENTS.md §Perf)."""
    B, S, D = x.shape
    out, aux = _moe_tokens(p, x.reshape(B * S, D), cfg, capacity_factor)
    return out.reshape(B, S, D), aux


def _moe_tokens(p, xt, cfg: ModelConfig, capacity_factor: float = 1.25):
    """xt: (T, D) flat tokens -> (T, D), plus aux load-balance loss."""
    T, D = xt.shape
    E, K = cfg.n_experts, cfg.top_k

    logits = (xt.astype(jnp.float32)) @ p["router"]  # (T, E)
    gates, idx = _topk_gates(logits, K)

    # aux loss (Switch): mean fraction routed x mean router prob
    probs = jax.nn.softmax(logits, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))

    capacity = int(max(K, T * K / E * capacity_factor))
    pos, keep = _positions_in_expert(idx, E, capacity)
    gates = gates * keep.astype(gates.dtype)

    # scatter tokens into the per-group (E*C, D) dispatch buffer. Inside the
    # group-vmap the scatter/gather stay group-local (the vmap dim carries
    # the data-axis sharding); E shards over 'model' (EP).
    flat_idx = (idx * capacity + jnp.minimum(pos, capacity - 1)).reshape(-1)  # (T*K,)
    src = jnp.repeat(xt, K, axis=0) * keep.reshape(-1, 1).astype(xt.dtype)
    buf = jnp.zeros((E * capacity, D), xt.dtype).at[flat_idx].add(src)
    buf = buf.reshape(E, capacity, D)
    buf = constrain(buf, "experts", "fsdp", None)

    # grouped expert FFN (SwiGLU)
    g = jnp.einsum("ecd,edf->ecf", buf, p["we_gate"].astype(xt.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, p["we_up"].astype(xt.dtype))
    h = jax.nn.silu(g) * u
    h = constrain(h, "experts", "fsdp", None)
    out_e = jnp.einsum("ecf,efd->ecd", h, p["we_down"].astype(xt.dtype))
    out_e = constrain(out_e, "experts", "fsdp", None)

    # combine: gather expert outputs back to tokens, weight by gates
    gathered = out_e.reshape(E * capacity, D)[flat_idx]  # (T*K, D)
    gathered = gathered * (gates.reshape(-1, 1).astype(xt.dtype)
                           * keep.reshape(-1, 1).astype(xt.dtype))
    out = jnp.sum(gathered.reshape(T, K, D), axis=1)
    return out, aux


def moe_apply_dense_ref(p, x, cfg: ModelConfig):
    """Oracle: run every expert densely, combine with top-k gates. O(E) FLOPs —
    test-only reference for the dispatch path."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    xt = x.reshape(-1, D)
    logits = xt.astype(jnp.float32) @ p["router"]
    gates, idx = _topk_gates(logits, K)
    g = jnp.einsum("td,edf->tef", xt, p["we_gate"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", xt, p["we_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    out_e = jnp.einsum("tef,efd->ted", h, p["we_down"].astype(x.dtype))
    mask = jnp.sum(jax.nn.one_hot(idx, E, dtype=gates.dtype) * gates[..., None], axis=1)  # (T,E)
    out = jnp.einsum("ted,te->td", out_e.astype(jnp.float32), mask)
    return out.reshape(B, S, D).astype(x.dtype)
