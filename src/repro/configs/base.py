"""Config dataclasses for models, shapes, meshes, privacy and runs.

Every assigned architecture gets one module in this package exporting
``CONFIG`` (the exact published config) and ``SMOKE`` (a reduced config of the
same family for CPU smoke tests). The full configs are exercised only via the
dry-run (ShapeDtypeStruct, no allocation).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional


# ---------------------------------------------------------------------------
# Model


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encoder | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    # attention / embedding options
    qkv_bias: bool = False
    rope: bool = True
    rope_theta: float = 1_000_000.0
    rope_pct: float = 1.0  # fraction of d_head that rotates (stablelm: 0.25)
    mrope: bool = False  # qwen2-vl multi-axis RoPE (position ids supplied)
    causal: bool = True  # False => encoder-only (hubert)
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_capacity_factor: float = 1.25  # smoke configs use dropless (=E)
    # SSM / RWKV / Mamba2
    ssm_state: int = 0
    rwkv_head_size: int = 64
    mamba_expand: int = 2
    mamba_conv: int = 4
    mamba_headdim: int = 64
    mamba_ngroups: int = 1  # B/C groups; head h reads group h // (heads / groups)
    mamba_chunk: int = 64  # SSD chunk length
    # hybrid (zamba2): the layers at `hybrid_layer_ids` run one of
    # `n_mem_blocks` parameter-shared attention+MLP blocks (alternately) over
    # concat(residual, embedding), with a per-invocation rank-`adapter_rank`
    # MLP adapter and output projection, before their mamba mixer
    hybrid_layer_ids: tuple[int, ...] = ()
    n_mem_blocks: int = 2
    adapter_rank: int = 0
    norm_eps: float = 1e-6  # RMSNorm eps
    # modality frontend stub: inputs arrive as precomputed embeddings
    frontend: str = "none"  # none | patches | frames
    # Megatron-style sequence parallelism: residuals between blocks are
    # sharded over the model axis on the sequence dim (EXPERIMENTS.md §Perf
    # iteration 2) — halves the TP collective traffic (all-reduce ->
    # reduce-scatter + all-gather) and divides residual memory by TP
    sequence_parallel: bool = False
    citation: str = ""

    # ---- derived ---------------------------------------------------------
    @property
    def attn_inner(self) -> int:
        return self.n_heads * self.d_head

    @property
    def kv_inner(self) -> int:
        return self.n_kv_heads * self.d_head

    def is_moe(self) -> bool:
        return self.n_experts > 0

    def has_attention(self) -> bool:
        return self.family in ("dense", "moe", "vlm", "encoder", "hybrid")

    def subquadratic(self) -> bool:
        """True if the arch supports O(S) decode state growth *and* the
        long-context shape (SSM / linear-attn / hybrid)."""
        return self.family in ("ssm", "hybrid")


def _per_layer_params(cfg: ModelConfig) -> tuple[int, int]:
    """(total, active) parameter count of one block (no embeddings)."""
    d = cfg.d_model
    if cfg.family == "ssm":  # RWKV6
        # token mix: r,k,v,g,o projections (d x d) + decay/bonus params +
        # lora-style data-dependent decay (small); channel mix: 2 mats
        tm = 5 * d * d + 4 * d  # r,k,v,g,output + per-channel decay/first
        lora = 6 * (d * 64 + 64 * d)  # data-dependent w/x lora (rank 64)
        cm = d * cfg.d_ff + cfg.d_ff * d
        p = tm + lora + cm + 4 * d  # + norms
        return p, p
    # attention block params
    attn = d * cfg.attn_inner + 2 * d * cfg.kv_inner + cfg.attn_inner * d
    if cfg.qkv_bias:
        attn += cfg.attn_inner + 2 * cfg.kv_inner
    norms = 2 * d
    # FFN params
    if cfg.is_moe():
        ffn_tot = cfg.n_experts * 3 * d * cfg.d_ff + d * cfg.n_experts  # + router
        ffn_act = cfg.top_k * 3 * d * cfg.d_ff + d * cfg.n_experts
    else:
        ffn_tot = ffn_act = 3 * d * cfg.d_ff  # SwiGLU: gate, up, down
    return attn + ffn_tot + norms, attn + ffn_act + norms


def _hybrid_params(cfg: ModelConfig) -> int:
    """Zamba2: every layer's Mamba2 mixer and norm, the shared blocks once
    each, one adapter pair and output projection per hybrid layer."""
    d, N, G = cfg.d_model, cfg.ssm_state, cfg.mamba_ngroups
    d_in = cfg.mamba_expand * d
    nh = d_in // cfg.mamba_headdim
    conv_dim = d_in + 2 * G * N
    mamba = (d * (d_in + conv_dim + nh)         # in_proj -> z, xBC, dt
             + (cfg.mamba_conv + 1) * conv_dim   # depthwise conv + bias
             + 3 * nh                            # A_log, D, dt_bias
             + d_in                              # gated group norm
             + d_in * d                          # out_proj
             + d)                                # the layer's input norm
    a = 2 * d
    shared = (a * cfg.attn_inner + 2 * a * cfg.kv_inner + cfg.attn_inner * d
              + 3 * d * cfg.d_ff + a + d)
    per_call = d * cfg.adapter_rank + cfg.adapter_rank * 2 * cfg.d_ff + d * d
    emb = cfg.vocab_size * d
    head = 0 if cfg.tie_embeddings else emb
    return (cfg.n_layers * mamba + cfg.n_mem_blocks * shared
            + len(cfg.hybrid_layer_ids) * per_call + emb + head + d)


def param_count(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return _hybrid_params(cfg)
    per, _ = _per_layer_params(cfg)
    emb = cfg.vocab_size * cfg.d_model
    head = 0 if cfg.tie_embeddings else cfg.vocab_size * cfg.d_model
    return cfg.n_layers * per + emb + head + cfg.d_model  # + final norm


def active_param_count(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return _hybrid_params(cfg)
    _, act = _per_layer_params(cfg)
    emb = cfg.vocab_size * cfg.d_model
    head = 0 if cfg.tie_embeddings else cfg.vocab_size * cfg.d_model
    return cfg.n_layers * act + emb + head + cfg.d_model


# ---------------------------------------------------------------------------
# Shapes (assigned input-shape set; seq_len x global_batch)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str  # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}


def shape_applicability(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """(runnable, reason-if-not). Skips documented in DESIGN.md §5."""
    if not cfg.causal and shape.kind == "decode":
        return False, "encoder-only arch has no decode step"
    if shape.name == "long_500k" and not cfg.subquadratic():
        return False, "long_500k needs sub-quadratic attention (pure full-attention arch)"
    return True, ""


def applicable_shapes(cfg: ModelConfig) -> list[ShapeConfig]:
    return [s for s in SHAPES.values() if shape_applicability(cfg, s)[0]]


# ---------------------------------------------------------------------------
# Privacy (the paper's knobs)


@dataclass(frozen=True)
class PrivacyConfig:
    enabled: bool = True
    sigma: float = 1.0  # noise multiplier (of C)
    clip_bound: float = 1.0  # C; initial bound when dynamic
    clip_mode: str = "per_silo"  # per_example | per_microbatch | per_silo
    dynamic_clip: bool = False
    clip_percentile: float = 0.5  # r, §4.3
    clip_percentile_max: float = 4.0  # fixed upper bound on C
    noise_lambda: float = 0.0  # λ, §4.4 noise correction ([0,1))
    delta: float = 1e-5
    mask_mode: str = "pairwise"  # admin | pairwise | none; DESIGN.md §2
    mask_scale: float = 8.0  # B/(σC): spread of the zero-sum r-terms
    mask_ring: bool = False  # int32 ring masking (exact cancellation)
    sync_path: str = "fused"  # fused | barrier (paper-faithful shard_map)
    # silo execution mode for the fused path:
    #   vmap — all silos batched at once (fast; per-silo grads transiently
    #          materialize: fine <= ~10B params)
    #   scan — silos processed sequentially, grads reduce-scattered into one
    #          fsdp-sharded fp32 accumulator (memory-optimal for 100B-scale;
    #          dynamic clipping uses the previous step's bound)
    silo_mode: str = "vmap"
    n_silos: int = 0  # 0 = auto (vmap: mesh silo count; scan: 4 data owners)


# ---------------------------------------------------------------------------
# Mesh / run


@dataclass(frozen=True)
class MeshConfig:
    shape: tuple[int, ...] = (16, 16)
    axes: tuple[str, ...] = ("data", "model")

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def silo_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.axes if a in ("pod", "data"))

    @property
    def n_silos(self) -> int:
        n = 1
        for a, s in zip(self.axes, self.shape):
            if a in ("pod", "data"):
                n *= s
        return n


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"  # sgd | momentum | adamw
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_accum: int = 1


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    mesh: MeshConfig = MeshConfig()
    privacy: PrivacyConfig = PrivacyConfig()
    optimizer: OptimizerConfig = OptimizerConfig()
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    seed: int = 0

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)


def reduce_for_smoke(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Reduced config of the same family: few layers, tiny width, small vocab."""
    kw = dict(
        name=cfg.name + "-smoke",
        n_layers=2,
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 4) if cfg.n_kv_heads < cfg.n_heads else 4,
        d_head=16,
        d_ff=128,
        vocab_size=256,
    )
    if cfg.is_moe():
        kw.update(n_experts=4, top_k=min(cfg.top_k, 2),
                  moe_capacity_factor=4.0)  # dropless: exact-match tests
    if cfg.family in ("ssm", "hybrid"):
        kw.update(ssm_state=16, rwkv_head_size=16, mamba_headdim=16)
    if cfg.family == "hybrid":
        # the published pattern in small: mamba and hybrid layers in order,
        # both shared blocks, 2 groups, adapters, attention over the concat
        kw.update(n_layers=6, hybrid_layer_ids=(2, 4), d_head=32,
                  adapter_rank=8, mamba_chunk=16)
    kw.update(overrides)
    return dataclasses.replace(cfg, **kw)
