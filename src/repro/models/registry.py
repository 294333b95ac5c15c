"""Unified model interface: ``build_model(cfg)`` returns a ``Model`` whose
functions close over the architecture config.

batch dicts:
  train:   {'tokens': (B,S) i32, 'labels': (B,S) i32}            (LM)
           {'embeds': (B,S,D), 'labels': (B,S), 'positions'?}    (vlm/audio)
  prefill: {'tokens': (B,S)} (+embeds/positions) -> (last_logits, cache)
  decode:  {'tokens': (B,1)} -> (logits (B,V), cache)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import hybrid, rwkv_stack, transformer


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[..., Any]
    loss: Callable[..., Any]
    init_cache: Callable[..., Any]
    prefill: Callable[..., Any]
    decode_step: Callable[..., Any]
    # paged serving surface (transformer and hybrid families; None elsewhere):
    # init_paged_cache(n_pages, page_size, n_slots=None) -> pool pytree;
    # paged_step(params, tokens, pool, tables, q_start, n_valid,
    #            logits_mode="last") -> (logits, pool) — one function for
    #   prefill chunks, decode, and (logits_mode="all") speculative verify
    init_paged_cache: Optional[Callable[..., Any]] = None
    paged_step: Optional[Callable[..., Any]] = None
    # families whose pool also holds a recurrent state slot per sequence:
    # reset_slot_state(pool, slot) -> pool, zeroing that slot's state on the
    # device (None where the pool is pages only)
    reset_slot_state: Optional[Callable[..., Any]] = None
    # dtype the paged pool/step run in — the scheduler needs it to build a
    # draft model that shares the target's page layout
    compute_dtype: Any = jnp.bfloat16


def build_model(cfg: ModelConfig, param_dtype=jnp.float32,
                compute_dtype=jnp.bfloat16, remat: bool = False,
                use_flash: bool = False) -> Model:
    if cfg.family in ("dense", "moe", "vlm", "encoder"):
        return Model(
            cfg=cfg,
            init=lambda key: transformer.init(key, cfg, param_dtype),
            loss=lambda p, b: transformer.loss_fn(p, cfg, b, use_flash, remat, compute_dtype),
            init_cache=lambda batch, max_len: transformer.init_cache(cfg, batch, max_len, compute_dtype),
            prefill=lambda p, b, c: transformer.prefill(p, cfg, b, c, compute_dtype),
            decode_step=lambda p, b, c: transformer.decode_step(p, cfg, b, c, compute_dtype),
            init_paged_cache=lambda n_pages, page_size, n_slots=None: \
                transformer.init_paged_cache(cfg, n_pages, page_size,
                                             compute_dtype),
            paged_step=lambda p, toks, pool, tables, q_start, n_valid, \
                logits_mode="last":
                transformer.forward_paged(p, cfg, toks, pool, tables,
                                          q_start, n_valid, compute_dtype,
                                          logits_mode),
            compute_dtype=compute_dtype,
        )
    if cfg.family == "ssm":
        return Model(
            cfg=cfg,
            init=lambda key: rwkv_stack.init(key, cfg, param_dtype),
            loss=lambda p, b: rwkv_stack.loss_fn(p, cfg, b, remat=remat, compute_dtype=compute_dtype),
            init_cache=lambda batch, max_len: rwkv_stack.init_state(cfg, batch, param_dtype),
            prefill=lambda p, b, c: rwkv_stack.decode_step(p, cfg, b, c, compute_dtype),
            decode_step=lambda p, b, c: rwkv_stack.decode_step(p, cfg, b, c, compute_dtype),
        )
    if cfg.family == "hybrid":
        return Model(
            cfg=cfg,
            init=lambda key: hybrid.init(key, cfg, param_dtype),
            loss=lambda p, b: hybrid.loss_fn(p, cfg, b, remat, compute_dtype, use_flash),
            init_cache=lambda batch, max_len: hybrid.init_state(cfg, batch, max_len, compute_dtype),
            prefill=lambda p, b, c: hybrid.decode_step(p, cfg, b, c, compute_dtype),
            decode_step=lambda p, b, c: hybrid.decode_step(p, cfg, b, c, compute_dtype),
            init_paged_cache=lambda n_pages, page_size, n_slots: \
                hybrid.init_paged_cache(cfg, n_pages, page_size, n_slots,
                                        compute_dtype),
            paged_step=lambda p, toks, pool, tables, q_start, n_valid, \
                logits_mode="last":
                hybrid.forward_paged(p, cfg, toks, pool, tables, q_start,
                                     n_valid, compute_dtype, logits_mode),
            reset_slot_state=hybrid.reset_slot_state,
            compute_dtype=compute_dtype,
        )
    raise ValueError(f"unknown family {cfg.family!r}")
