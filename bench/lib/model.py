"""A configuration file (Hugging Face ``config.json`` keys) read into the
sizes the benchmark needs, the program's ``ModelConfig``, and the operation
and byte counts every roofline and MFU metric divides by.

Counts are model counts: what the published architecture needs, not what
the program happens to compute (recomputation, padding rows and masked
attention scores are not counted).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

F32 = 4  # bytes per element; the configurations run in float32


@dataclasses.dataclass(frozen=True)
class Sizes:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    tied: bool
    rope_theta: float
    norm_eps: float

    # -- parameter counts ---------------------------------------------------
    @property
    def layer_matmul_params(self) -> int:
        d, q, kv = self.d_model, self.n_heads * self.d_head, \
            self.n_kv_heads * self.d_head
        return d * q + 2 * d * kv + q * d + 3 * d * self.d_ff

    @property
    def layer_params(self) -> int:
        bias = (self.n_heads + 2 * self.n_kv_heads) * self.d_head \
            if self.qkv_bias else 0
        return self.layer_matmul_params + bias + 2 * self.d_model

    @property
    def head_params(self) -> int:
        return self.vocab * self.d_model

    @property
    def params(self) -> int:
        emb = self.vocab * self.d_model
        return self.n_layers * self.layer_params + emb \
            + (0 if self.tied else self.head_params) + self.d_model

    @property
    def matmul_params(self) -> int:
        """Parameters a token multiplies: every layer matrix and the head
        (tied or not); the embedding lookup is a gather and is not counted."""
        return self.n_layers * self.layer_matmul_params + self.head_params

    # -- operation counts ---------------------------------------------------
    def attn_flops_fwd(self, ctx: float) -> float:
        """Score and value matmuls of one query token against ``ctx`` keys,
        all layers, forward."""
        return 4.0 * self.n_layers * self.n_heads * self.d_head * ctx

    def train_flops_per_token(self, seq_len: int) -> float:
        """6 x matmul parameters plus 3 x the forward attention matmuls.
        Causal attention counts half: a query at position i attends to
        i + 1 keys, (S + 1) / 2 on average over the sequence."""
        return 6.0 * self.matmul_params \
            + 3.0 * self.attn_flops_fwd((seq_len + 1) / 2)

    # -- byte counts --------------------------------------------------------
    @property
    def weight_bytes(self) -> int:
        """Weights one forward call must read: every layer matrix, bias and
        norm, the final norm and the head (the embedding gather reads only
        the rows of its tokens, counted as nothing)."""
        return F32 * (self.n_layers * self.layer_params + self.head_params
                      + self.d_model)

    def kv_bytes_per_token(self) -> int:
        """K and V of one cached token, all layers."""
        return 2 * F32 * self.n_layers * self.n_kv_heads * self.d_head


def load(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def sizes(cfg: dict, name: str = "") -> Sizes:
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    bias = cfg.get("attention_bias")
    if bias is None:
        bias = cfg.get("model_type") == "qwen2"  # Qwen2 biases q, k and v
    return Sizes(
        name=name or cfg.get("model_type", "model"),
        n_layers=int(cfg["num_hidden_layers"]), d_model=d, n_heads=heads,
        n_kv_heads=int(cfg.get("num_key_value_heads", heads)),
        d_head=int(cfg.get("head_dim") or d // heads),
        d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        qkv_bias=bool(bias), tied=bool(cfg.get("tie_word_embeddings", False)),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        norm_eps=float(cfg.get("rms_norm_eps", 1e-6)))


def program_config(s: Sizes):
    """The program's ``ModelConfig`` for these sizes (a dense decoder)."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=s.name, family="dense", n_layers=s.n_layers, d_model=s.d_model,
        n_heads=s.n_heads, n_kv_heads=s.n_kv_heads, d_head=s.d_head,
        d_ff=s.d_ff, vocab_size=s.vocab, qkv_bias=s.qkv_bias,
        rope_theta=s.rope_theta, tie_embeddings=s.tied)
