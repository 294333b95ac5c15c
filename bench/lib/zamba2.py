"""Zamba2 configurations: a configuration file (Hugging Face ``config.json``
keys of ``Zamba2ForCausalLM``) read into sizes, the program's
``ModelConfig``, the weights made from ``--seed``, and the operation and byte
counts the Zamba2 readers divide by (in place of ``bench/lib/model.py``'s
dense ``Sizes``, which the harness hands every driver and reader).

Counts are model counts, as in ``model.py``: what the published
architecture needs, not what the program computes (padding rows, masked
scores and recomputation are not counted).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from pathlib import Path

from bench.lib.model import F32
from bench.lib.weights import seed31  # noqa: F401  (the serving driver's name)

BENCH = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Sizes:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    d_head: int
    d_ff: int
    vocab: int
    ssm_state: int
    expand: int
    headdim: int
    ngroups: int
    conv: int
    chunk: int
    hybrid_ids: tuple
    n_mem_blocks: int
    adapter_rank: int
    rope_theta: float
    norm_eps: float
    tied: bool
    init_range: float

    # -- widths -------------------------------------------------------------
    @property
    def d_in(self) -> int:
        return self.expand * self.d_model

    @property
    def nh(self) -> int:
        return self.d_in // self.headdim

    @property
    def conv_dim(self) -> int:
        return self.d_in + 2 * self.ngroups * self.ssm_state

    @property
    def attn_in(self) -> int:
        return 2 * self.d_model

    @property
    def attn_inner(self) -> int:
        return self.n_heads * self.d_head

    @property
    def n_calls(self) -> int:
        """Shared-block invocations (hybrid layers)."""
        return len(self.hybrid_ids)

    # -- parameter counts ---------------------------------------------------
    @property
    def mamba_matmul_params(self) -> int:
        d = self.d_model
        return d * (self.d_in + self.conv_dim + self.nh) + self.d_in * d

    @property
    def shared_matmul_params(self) -> int:
        """One invocation: q, k, v, o, the MLP, the adapter pair and the
        output projection."""
        d, r = self.d_model, self.adapter_rank
        return (3 * self.attn_in * self.attn_inner + self.attn_inner * d
                + 3 * d * self.d_ff + d * r + r * 2 * self.d_ff + d * d)

    @property
    def head_params(self) -> int:
        return self.vocab * self.d_model

    @property
    def matmul_params(self) -> int:
        """Parameters a token multiplies: every mixer, every invocation and
        the head (tied or not)."""
        return (self.n_layers * self.mamba_matmul_params
                + self.n_calls * self.shared_matmul_params + self.head_params)

    @property
    def params(self) -> int:
        d, a = self.d_model, self.attn_in
        mamba = self.mamba_matmul_params + (self.conv + 1) * self.conv_dim \
            + 3 * self.nh + self.d_in + d
        block = (3 * a * self.attn_inner + self.attn_inner * d
                 + 3 * d * self.d_ff + a + d)
        per_call = d * self.adapter_rank + self.adapter_rank * 2 * self.d_ff \
            + d * d
        return (self.n_layers * mamba + self.n_mem_blocks * block
                + self.n_calls * per_call + self.head_params
                + (0 if self.tied else self.head_params) + d)

    # -- operation counts ---------------------------------------------------
    @property
    def scan_flops_per_token(self) -> float:
        """The state recurrence of one token, all layers: the (nh, P, N)
        state's decay and update (2 per element) and its read-out (2)."""
        return 4.0 * self.n_layers * self.nh * self.headdim * self.ssm_state

    def attn_flops_fwd(self, ctx: float) -> float:
        """Score and value matmuls of one query token against ``ctx`` keys,
        every invocation."""
        return 4.0 * self.n_calls * self.n_heads * self.d_head * ctx

    # -- byte counts --------------------------------------------------------
    @property
    def weight_bytes(self) -> int:
        """Weights one forward call reads: the embedding gather counts as
        nothing, the head (the embedding, when tied) is read once."""
        return F32 * (self.params - (0 if self.tied else self.head_params))

    def kv_bytes_per_token(self) -> int:
        return 2 * F32 * self.n_calls * self.n_heads * self.d_head

    @property
    def state_bytes_per_slot(self) -> int:
        return F32 * self.n_layers * (self.nh * self.headdim * self.ssm_state
                                      + (self.conv - 1) * self.conv_dim)


def sizes(cfg: dict, name: str = "") -> Sizes:
    d = int(cfg["hidden_size"])
    L = int(cfg["num_hidden_layers"])
    return Sizes(
        name=name or cfg.get("model_type", "zamba2"), n_layers=L, d_model=d,
        n_heads=int(cfg["num_attention_heads"]),
        d_head=int(cfg["attention_head_dim"]),
        d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        ssm_state=int(cfg["mamba_d_state"]), expand=int(cfg["mamba_expand"]),
        headdim=int(cfg["mamba_headdim"]), ngroups=int(cfg["mamba_ngroups"]),
        conv=int(cfg["mamba_d_conv"]), chunk=int(cfg["chunk_size"]),
        hybrid_ids=tuple(int(i) for i in cfg["hybrid_layer_ids"] if i < L),
        n_mem_blocks=int(cfg["num_mem_blocks"]),
        adapter_rank=int(cfg["adapter_rank"]),
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        norm_eps=float(cfg["rms_norm_eps"]),
        tied=bool(cfg.get("tie_word_embeddings", True)),
        init_range=float(cfg.get("initializer_range", 0.02)))


def load(name: str) -> Sizes:
    import json

    return sizes(json.loads((BENCH / "configs" / f"{name}.json").read_text()),
                 name)


def for_run(run) -> Sizes | None:
    """The Zamba2 sizes of a reader's run, from the configuration the
    serving driver names in its records."""
    name = run.records.get("zamba2_config")
    return load(name) if name else None


def program_config(s: Sizes):
    """The program's ``ModelConfig`` for these sizes (the hybrid family)."""
    from repro.configs.base import ModelConfig

    assert s.n_heads * s.d_head == s.attn_in, "attention over concat(x, e)"
    return ModelConfig(
        name=s.name, family="hybrid", n_layers=s.n_layers, d_model=s.d_model,
        n_heads=s.n_heads, n_kv_heads=s.n_heads, d_head=s.d_head,
        d_ff=s.d_ff, vocab_size=s.vocab, rope_theta=s.rope_theta,
        tie_embeddings=s.tied, ssm_state=s.ssm_state, mamba_expand=s.expand,
        mamba_conv=s.conv, mamba_headdim=s.headdim, mamba_ngroups=s.ngroups,
        mamba_chunk=s.chunk, hybrid_layer_ids=s.hybrid_ids,
        n_mem_blocks=s.n_mem_blocks, adapter_rank=s.adapter_rank,
        norm_eps=s.norm_eps)


# ---------------------------------------------------------------------------
# weights: the program's parameter tree, made on the device from the seed


def shapes(s: Sizes) -> dict:
    L, d, ff, K, nb = s.n_layers, s.d_model, s.d_ff, s.n_calls, \
        s.n_mem_blocks
    a, q, r = s.attn_in, s.attn_inner, s.adapter_rank
    tree = {
        "embed": (s.vocab, d),
        "mamba_layers": {
            "ln": {"scale": (L, d)},
            "mamba": {"in_proj": (L, d, s.d_in + s.conv_dim + s.nh),
                      "conv_w": (L, s.conv, s.conv_dim),
                      "conv_b": (L, s.conv_dim), "A_log": (L, s.nh),
                      "D": (L, s.nh), "dt_bias": (L, s.nh),
                      "gn_scale": (L, s.d_in),
                      "out_proj": (L, s.d_in, d)}},
        "shared": {"ln_in": {"scale": (nb, a)},
                   "attn": {"wq": (nb, a, q), "wk": (nb, a, q),
                            "wv": (nb, a, q), "wo": (nb, q, d)},
                   "ln_ff": {"scale": (nb, d)},
                   "mlp": {"w_gate": (nb, d, ff), "w_up": (nb, d, ff),
                           "w_down": (nb, ff, d)}},
        "hybrid": {"adapter_a": (K, d, r), "adapter_gate": (K, r, ff),
                   "adapter_up": (K, r, ff), "linear": (K, d, d)},
        "ln_f": {"scale": (d,)},
    }
    if not s.tied:
        tree["lm_head"] = (d, s.vocab)
    return tree


def _leaf(key, path: str, shape, init_range: float, conv: int):
    """Matrices N(0, initializer_range); norm scales and D 1 + N(0, 0.02);
    the conv N(0, 1/sqrt(its width)) with a small bias; A_log the log of
    U(1, 16) and dt_bias the inverse softplus of a dt log-uniform in
    [1e-3, 1e-1], as Zamba2 initialises them."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    leaf = path.split("/")[-1]
    if leaf == "A_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if leaf == "dt_bias":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    z = jax.random.normal(k, shape, jnp.float32)
    if leaf in ("scale", "gn_scale", "D"):
        return 1.0 + 0.02 * z
    if leaf == "conv_w":
        return z / math.sqrt(conv)
    if leaf == "conv_b":
        return 0.02 * z
    return init_range * z


def _walk(tree, prefix=""):
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from _walk(v, p)
        else:
            yield p, v


@functools.lru_cache(maxsize=4)
def _maker(s: Sizes):
    import jax

    leaves = tuple(_walk(shapes(s)))

    @jax.jit
    def make(seed):
        key = jax.random.PRNGKey(seed)
        out: dict = {}
        for path, shp in leaves:
            node = out
            parts = path.split("/")
            for k in parts[:-1]:
                node = node.setdefault(k, {})
            node[parts[-1]] = _leaf(key, path, shp, s.init_range, s.conv)
        return out
    return make


def make(s: Sizes, seed: int):
    """The parameter tree for ``seed``, float32, in one jitted call."""
    return _maker(s)(seed31(seed))


# ---------------------------------------------------------------------------
# serving counts, per program call: (chunk width C, n_valid (B,), q_start (B,))


def call_flops(s: Sizes, n_valid, q_start) -> float:
    """Model FLOPs of the valid rows of one call: every matrix per valid
    token, the state recurrence, causal attention of each invocation over
    the cache and the chunk, and the head for each slot that makes a token."""
    f = 0.0
    for n, q in zip(n_valid, q_start):
        n, q = int(n), int(q)
        if n <= 0:
            continue
        f += (2.0 * (s.matmul_params - s.head_params) + s.scan_flops_per_token) * n
        f += s.attn_flops_fwd(n * q + n * (n + 1) / 2)
        f += 2.0 * s.head_params
    return f


def call_bytes(s: Sizes, n_valid, q_start, page: int) -> float:
    """The weights once, the live KV pages of every valid slot, and each
    valid slot's recurrent state read and written."""
    live = [(int(n), int(q)) for n, q in zip(n_valid, q_start) if int(n) > 0]
    if not live:
        return 0.0
    toks = sum(math.ceil((q + n) / page) * page for n, q in live)
    return (s.weight_bytes + toks * s.kv_bytes_per_token()
            + 2 * len(live) * s.state_bytes_per_slot)


def least_time(s: Sizes, peaks: dict, call, page: int) -> float:
    _, n_valid, q_start = call
    return max(call_flops(s, n_valid, q_start) / peaks["flops_per_s"],
               call_bytes(s, n_valid, q_start, page) / peaks["hbm_bytes_per_s"])


def ssd_kernel_counts(s: Sizes, slots: int, C: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the SSD Pallas kernel over one chunk call of width
    ``C``, all layers, from its shapes: per (slot, head) and chunk of
    ``chunk`` rows, the C.h, C.B^T, scores.(dt x) and state-update matmuls,
    and x, dt, log decay, B, C read, y written, the state read and written."""
    c = min(s.chunk, C)
    P, N = s.headdim, s.ssm_state
    blocks = slots * s.nh * (C // c)
    flops = blocks * 2.0 * c * (2 * N * P + c * N + c * P)
    byts = blocks * F32 * (2 * c * P + 2 * c + 2 * c * N) \
        + slots * s.nh * F32 * 2 * P * N
    return s.n_layers * flops, s.n_layers * byts


def window_calls(run):
    """The program calls of the ticks inside the window."""
    calls = run.records.get("calls", [])
    return [c for _, _, a, b in run.records.get("ticks", []) for c in calls[a:b]]


def tick_share(run):
    """As ``readers.tick_share`` with Zamba2's counts (%)."""
    s = for_run(run)
    if s is None:
        return None
    page = run.mix["server"]["page_size"]
    calls, num, den = run.records.get("calls", []), 0.0, 0.0
    for t0, t1, a, b in run.records.get("ticks", []):
        num += sum(least_time(s, run.peaks, c, page) for c in calls[a:b])
        den += t1 - t0
    return 100.0 * num / den if den > 0 and num > 0 else None

