"""zamba2-7b (Zamba2-7B-Instruct) — hybrid 81L d_model=3584, 81 Mamba2
mixers (112 heads of 64, state 64, 2 groups, conv 4) and 2 parameter-shared
attention+MLP blocks used alternately before the 13 mixers at
``hybrid_layer_ids``: 32 MHA heads of 224 over concat(x, embedding), 7168
wide; gated-GELU MLP 14336 with a rank-128 adapter per invocation; vocab
32000, tied head.

Source: https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json
(the layer equations and the departures from HF ``modeling_zamba2`` are in
``models/hybrid.py``).
"""
from repro.configs.base import ModelConfig, reduce_for_smoke

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    d_head=224,            # attention_head_dim, over attention_hidden_size 7168
    d_ff=14336,
    vocab_size=32000,
    ssm_state=64,
    mamba_expand=2,
    mamba_headdim=64,
    mamba_conv=4,
    mamba_ngroups=2,
    mamba_chunk=256,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    n_mem_blocks=2,
    adapter_rank=128,
    norm_eps=1e-5,
    rope=True,
    rope_theta=10_000.0,
    tie_embeddings=True,   # Zamba2Config's default; the file lacks the key
    citation="https://huggingface.co/Zyphra/Zamba2-7B-Instruct/blob/main/config.json",
)

SMOKE = reduce_for_smoke(CONFIG, n_kv_heads=4)
