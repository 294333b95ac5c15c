"""Weights and token rows made from ``--seed`` on the device, in one jitted
call each.

The benchmark makes the weights, not the program: the program and the plain
reference are both handed these arrays. The layout is the program's
parameter tree (a dense decoder: ``embed``, stacked ``layers``, ``ln_f``,
``lm_head`` when untied). Matrices and embeddings are N(0, initializer_range)
as in the published initialisation; biases are small and non-zero so the
bias path is exercised; norm scales are 1 + N(0, 0.02).
"""
from __future__ import annotations

import functools
import zlib

import numpy as np

from bench.lib.model import Sizes


def seed31(seed: int) -> int:
    """Any whole number (seeds may exceed 32 bits) to a
    non-negative int32, through numpy's SeedSequence."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


def shapes(s: Sizes) -> dict:
    L, d, ff = s.n_layers, s.d_model, s.d_ff
    q, kv = s.n_heads * s.d_head, s.n_kv_heads * s.d_head
    attn = {"wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
            "wo": (L, q, d)}
    if s.qkv_bias:
        attn.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
    tree = {"embed": (s.vocab, d),
            "layers": {"ln1": {"scale": (L, d)}, "attn": attn,
                       "ln2": {"scale": (L, d)},
                       "ffn": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                               "w_down": (L, ff, d)}},
            "ln_f": {"scale": (d,)}}
    if not s.tied:
        tree["lm_head"] = (d, s.vocab)
    return tree


def _leaf(key, path: str, shape, init_range: float, dtype):
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(k, shape, jnp.float32)
    if path.endswith("scale"):
        w = 1.0 + 0.02 * z
    elif path.split("/")[-1] in ("bq", "bk", "bv"):
        w = 0.02 * z
    else:
        w = init_range * z
    return w.astype(dtype)


def _walk(tree, prefix=""):
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from _walk(v, p)
        else:
            yield p, v


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for k in parts[:-1]:
            node = node.setdefault(k, {})
        node[parts[-1]] = v
    return out


@functools.lru_cache(maxsize=8)
def _maker(s: Sizes, init_range: float, dtype_name: str):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    leaves = tuple(_walk(shapes(s)))

    @jax.jit
    def make(seed):
        key = jax.random.PRNGKey(seed)
        return _nest({p: _leaf(key, p, shp, init_range, dtype)
                      for p, shp in leaves})
    return make


def make(s: Sizes, seed: int, init_range: float = 0.02,
         dtype: str = "float32"):
    """The parameter tree for ``seed`` (one jitted call, on the default
    device)."""
    return _maker(s, float(init_range), dtype)(seed31(seed))


@functools.lru_cache(maxsize=8)
def _token_maker(n_batches: int, rows: int, width: int, vocab: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(seed):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x70C5)
        return jax.random.randint(key, (n_batches, rows, width), 0, vocab,
                                  jnp.int32)
    return make


def token_rows(seed: int, n_batches: int, rows: int, width: int, vocab: int):
    """``(n_batches, rows, width)`` int32 token ids, uniform over the
    vocabulary, drawn on the device from ``seed``."""
    return _token_maker(n_batches, rows, width, vocab)(seed31(seed))
