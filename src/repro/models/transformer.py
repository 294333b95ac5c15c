"""Decoder / encoder transformer stack (dense, MoE, VLM, audio-encoder
families) with scan-over-layers, optional remat, KV-cache decode.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding_rules import constrain
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.layers import (
    chunked_lm_loss,
    cross_entropy,
    dense_init,
    embed_init,
    rmsnorm,
    rmsnorm_init,
    swiglu_apply,
    swiglu_init,
)

AUX_LOSS_WEIGHT = 0.01


def layer_init(key, cfg: ModelConfig, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "attn": attn.attn_init(k1, cfg, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
    }
    if cfg.is_moe():
        p["moe"] = moe_mod.moe_init(k2, cfg, dtype)
    else:
        p["ffn"] = swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def layer_apply(p, x, cfg: ModelConfig, positions, cache_entry=None, use_flash=False):
    h, new_cache = attn.attn_apply(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                                   cfg, positions, cache_entry, use_flash)
    x = x + h
    y = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.is_moe():
        f, aux = moe_mod.moe_apply(p["moe"], y, cfg, cfg.moe_capacity_factor)
    else:
        f, aux = swiglu_apply(p["ffn"], y), jnp.zeros((), jnp.float32)
    x = x + f
    if cfg.sequence_parallel:
        x = constrain(x, "batch", "seq_tp", None)
    else:
        x = constrain(x, "batch", None, None)
    return x, new_cache, aux


def init(key, cfg: ModelConfig, dtype=jnp.float32):
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    params = {
        "embed": embed_init(ke, cfg.vocab_size, cfg.d_model, dtype),
        "layers": jax.vmap(lambda k: layer_init(k, cfg, dtype))(layer_keys),
        "ln_f": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kh, cfg.d_model, cfg.vocab_size, dtype)
    return params


def _embed_inputs(params, cfg, batch, compute_dtype):
    if "embeds" in batch:  # modality frontend stub (vlm / audio)
        x = batch["embeds"].astype(compute_dtype)
    else:
        x = params["embed"].astype(compute_dtype)[batch["tokens"]]
    return constrain(x, "batch", None, None)


def _positions(cfg, batch, S, B, offset=0):
    if "positions" in batch:
        return batch["positions"]
    return jnp.broadcast_to(offset + jnp.arange(S)[None], (B, S))


def forward(params, cfg: ModelConfig, batch: dict, cache=None, use_flash=False,
            remat=False, compute_dtype=jnp.bfloat16, logits_mode="all"):
    """Returns (logits, new_cache, aux). logits_mode: all | last."""
    x = _embed_inputs(params, cfg, batch, compute_dtype)
    B, S, _ = x.shape
    offset = 0 if cache is None else cache["len"][0]
    positions = _positions(cfg, batch, S, B, offset)

    if cache is None:
        def body(h, lp):
            h, _, aux = layer_apply(lp, h, cfg, positions, None, use_flash)
            return h, aux
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, auxs = jax.lax.scan(body, x, params["layers"])
        new_cache = None
    else:
        def body_c(h, inp):
            lp, ce = inp
            h, nc, aux = layer_apply(lp, h, cfg, positions, ce, use_flash)
            return h, (nc, aux)
        x, (new_cache, auxs) = jax.lax.scan(body_c, x, (params["layers"], cache))

    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    if logits_mode == "hidden":
        return x, new_cache, jnp.sum(auxs)
    if logits_mode == "last":
        x = x[:, -1:]
    head = params.get("lm_head", None)
    if head is None:
        head = params["embed"].T
    logits = x @ head.astype(x.dtype)
    logits = constrain(logits, "batch", None, "vocab")
    return logits, new_cache, jnp.sum(auxs)


def loss_fn(params, cfg: ModelConfig, batch: dict, use_flash=False, remat=False,
            compute_dtype=jnp.bfloat16):
    hidden, _, aux = forward(params, cfg, batch, None, use_flash, remat,
                             compute_dtype, logits_mode="hidden")
    head = params.get("lm_head", None)
    if head is None:
        head = params["embed"].T
    loss = chunked_lm_loss(hidden, head, batch["labels"])
    if cfg.is_moe():
        loss = loss + AUX_LOSS_WEIGHT * aux
    return loss


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    return attn.init_kv_cache(cfg, batch, max_len, cfg.n_layers, dtype)


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=jnp.bfloat16):
    return attn.init_paged_pool(cfg, n_pages, page_size, cfg.n_layers, dtype)


def layer_apply_paged(p, x, cfg: ModelConfig, positions, k_pages, v_pages,
                      tables, q_start, n_valid):
    with jax.named_scope("paged.qkv"):
        y = rmsnorm(p["ln1"], x, cfg.norm_eps)
    h, k_pages, v_pages = attn.attn_apply_paged(
        p["attn"], y, cfg, positions, k_pages, v_pages, tables, q_start,
        n_valid)
    x = x + h
    with jax.named_scope("paged.mlp"):
        y = rmsnorm(p["ln2"], x, cfg.norm_eps)
        if cfg.is_moe():
            f, _ = moe_mod.moe_apply(p["moe"], y, cfg, cfg.moe_capacity_factor)
        else:
            f = swiglu_apply(p["ffn"], y)
        x = x + f
    return x, k_pages, v_pages


def forward_paged(params, cfg: ModelConfig, tokens, pages: dict, tables,
                  q_start, n_valid, compute_dtype=jnp.bfloat16,
                  logits_mode="last"):
    """One serving step over the paged pool: C new tokens per slot (C > 1 =
    a prefill chunk, C == 1 = decode; both shapes share this one function,
    so the scheduler keeps exactly two compiled graphs).

    tokens (B, C) i32; tables (B, nP) i32; q_start (B,) tokens already
    cached per slot; n_valid (B,) how many of the C are real (0 = inactive
    slot — its row computes garbage on zeroed pages and writes nothing).

    logits_mode "last" returns (B, V) logits of each slot's last valid
    token (the prefill/decode shape). "all" returns (B, C, V) logits at
    every chunk position — the speculative-verify read-out, where position
    c scores the token *following* tokens[:, c]. Both modes run the same
    layer stack, so a chunk-shaped "all" graph is the only addition the
    speculative scheduler needs for verification.

    Device ops carry named scopes for a profile: ``paged.qkv``,
    ``paged.kv_write``, ``paged.attention`` and ``paged.mlp`` in each layer,
    ``paged.head`` on both uses of the vocabulary table (the embedding
    lookup, and the final norm with the read-out)."""
    with jax.named_scope("paged.head"):
        x = params["embed"].astype(compute_dtype)[tokens]
    B, S = tokens.shape
    positions = q_start[:, None] + jnp.arange(S)[None, :]

    def body(h, inp):
        lp, kp, vp = inp
        h, kp, vp = layer_apply_paged(lp, h, cfg, positions, kp, vp,
                                      tables, q_start, n_valid)
        return h, (kp, vp)

    x, (k_pages, v_pages) = jax.lax.scan(
        body, x, (params["layers"], pages["k_pages"], pages["v_pages"]))
    pages = {"k_pages": k_pages, "v_pages": v_pages}
    with jax.named_scope("paged.head"):
        x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
        head = params.get("lm_head", None)
        if head is None:
            head = params["embed"].T
        if logits_mode == "all":
            return x @ head.astype(x.dtype), pages              # (B, C, V)
        last = jnp.clip(n_valid - 1, 0, S - 1)                  # (B,)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)  # (B, 1, D)
        return (x @ head.astype(x.dtype))[:, 0], pages


def prefill(params, cfg: ModelConfig, batch: dict, cache, compute_dtype=jnp.bfloat16):
    logits, cache, _ = forward(params, cfg, batch, cache,
                               compute_dtype=compute_dtype, logits_mode="last")
    return logits[:, 0], cache


def decode_step(params, cfg: ModelConfig, batch: dict, cache, compute_dtype=jnp.bfloat16):
    logits, cache, _ = forward(params, cfg, batch, cache,
                               compute_dtype=compute_dtype, logits_mode="last")
    return logits[:, 0], cache
