"""Logical-axis sharding rules (DP / FSDP / TP / EP / SP).

Models annotate tensors with *logical* axis names; the rules map them to mesh
axes. The mapping (DESIGN.md §6):

  batch    -> ("pod", "data")     data parallel over silos
  seq      -> ("pod", "data")     sequence parallel (only used where batch=1,
                                  e.g. long-context KV caches / encoder SP)
  heads    -> "model"             tensor parallel (Megatron attention split)
  kv_heads -> "model"             (replicated automatically if indivisible)
  ff       -> "model"             tensor parallel (FFN hidden)
  vocab    -> "model"             tensor parallel (embedding / logits)
  experts  -> "model"             expert parallel
  fsdp     -> "data"              parameter/optimizer sharding (ZeRO-3 style;
                                  within-pod so layer all-gathers stay on ICI)
  (anything else) -> replicated

A constraint axis is silently dropped when the dim is not divisible by the
mesh-axis size (e.g. kv_heads=8 on model=16 -> replicate) — degrade, don't
fail. Outside a mesh context the helpers are no-ops so model code stays
mesh-agnostic (smoke tests run on 1 CPU device).
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
from jax.sharding import AxisType, PartitionSpec as P

Axis = Union[None, str, tuple[str, ...]]

RULES: dict[str, Axis] = {
    "batch": ("pod", "data"),
    "seq": ("pod", "data"),
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "seq_tp": "model",  # Megatron sequence parallelism (residuals)
    "fsdp": "data",
    "dhead": None,
    "dmodel": None,
    "layers": None,
    None: None,
}


def current_mesh():
    """The mesh governing the current trace, or None outside any mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def auto_axis_names(mesh) -> set:
    """Axis names currently in Auto mode (usable in sharding constraints)."""
    return {n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t == AxisType.Auto}


def _present_axes(mesh, axis: Axis) -> Optional[Axis]:
    """Prune mesh axes absent from the current mesh (e.g. 'pod' on the
    single-pod mesh) or currently Manual (inside shard_map regions only the
    Auto axes may appear in sharding constraints); None if nothing remains."""
    if axis is None:
        return None
    names = (axis,) if isinstance(axis, str) else axis
    auto = auto_axis_names(mesh)
    kept = tuple(a for a in names
                 if a in mesh.axis_names and mesh.shape[a] > 1 and a in auto)
    if not kept:
        return None
    return kept[0] if len(kept) == 1 else kept


def _axis_size(mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    names = (axis,) if isinstance(axis, str) else axis
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def spec_for(logical: Sequence[Optional[str]], dims: Optional[Sequence[int]] = None,
             rules: Optional[dict] = None) -> P:
    """PartitionSpec from logical names, with divisibility fallback."""
    mesh = current_mesh()
    rules = rules or RULES
    out = []
    for i, name in enumerate(logical):
        axis = rules.get(name, None)
        if axis is None or mesh is None:
            out.append(None)
            continue
        axis = _present_axes(mesh, axis)
        if axis is None:
            out.append(None)
            continue
        size = _axis_size(mesh, axis)
        if size <= 1:
            out.append(None)
            continue
        if dims is not None and dims[i] % size != 0:
            out.append(None)  # degrade to replication
            continue
        out.append(axis)
    return P(*out)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axis names; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = spec_for(logical, dims=x.shape)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter specs: map a params pytree (nested dicts of arrays) to
# PartitionSpecs by key-path naming conventions.

# (suffix or key) -> logical names for the *trailing* dims of that tensor.
# Leading stacked-layer dims are always replicated.
_PARAM_RULES: list[tuple[str, tuple[Optional[str], ...]]] = [
    ("embed", ("vocab", "fsdp")),
    ("lm_head", ("fsdp", "vocab")),
    ("wq", ("fsdp", "heads")),
    ("wk", ("fsdp", "kv_heads")),
    ("wv", ("fsdp", "kv_heads")),
    ("wo", ("heads", "fsdp")),
    ("bq", ("heads",)),
    ("bk", ("kv_heads",)),
    ("bv", ("kv_heads",)),
    ("w_gate", ("fsdp", "ff")),
    ("w_up", ("fsdp", "ff")),
    ("w_down", ("ff", "fsdp")),
    ("router", ("fsdp", "experts")),
    # expert weights: EP over the model axis on dim E; the per-expert matmul
    # dims get FSDP (both EP+TP on one mesh axis would duplicate it)
    ("we_gate", ("experts", "fsdp", None)),
    ("we_up", ("experts", "fsdp", None)),
    ("we_down", ("experts", None, "fsdp")),
    # rwkv6 / mamba2
    ("w_in", ("fsdp", "ff")),
    ("w_out", ("ff", "fsdp")),
    ("in_proj", ("fsdp", "ff")),
    ("out_proj", ("ff", "fsdp")),
    ("wr", ("fsdp", "heads")),  # rwkv time-mix receptance (head-TP)
    ("wg", ("fsdp", "heads")),  # rwkv time-mix gate
    ("w_recept", ("fsdp", "ff")),  # rwkv channel-mix receptance
    ("scale", ("fsdp",)),
]


def _match(path: str) -> Optional[tuple[Optional[str], ...]]:
    last = path.rsplit("/", 1)[-1]
    for key, names in _PARAM_RULES:
        if last == key:
            return names
    return None


def params_pspecs(params) -> "jax.tree_util.PyTreeDef":
    """PartitionSpec pytree matching ``params`` (call under a mesh context)."""
    mesh = current_mesh()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def one(path, x):
        keys = "/".join(getattr(k, "key", str(k)) for k in path)
        names = _match(keys)
        nd = x.ndim
        if names is None or mesh is None:
            return P()
        # right-align logical names to trailing dims; leading dims replicated
        logical = [None] * (nd - len(names)) + list(names)
        return spec_for(logical[:nd] if nd >= len(names) else logical[-nd:],
                        dims=x.shape)

    specs = [one(p, x) for p, x in flat]
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params), specs)


def named_shardings(mesh, pspecs):
    from jax.sharding import NamedSharding
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda s: isinstance(s, P))
