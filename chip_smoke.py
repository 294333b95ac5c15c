#!/usr/bin/env python3
"""Bring-up smoke test: both device paths on a TPU at qwen2.5-3b widths.

One process, one chip, the normal entry points:

* **train** -- a few ``Session.train`` steps on the fused tier with privacy
  on: pairwise zero-sum masks, noise correction (lambda > 0), AdamW, seeded
  synthetic tokens;
* **serve** -- seeded requests through ``ContinuousServer`` with the trained
  params, then a short pass with prefix sharing and speculative decoding, so
  that ``paged_rollback`` and the ``logits_mode="all"`` verify graph run too.

Every width of qwen2.5-3b is as published (``configs/qwen25_3b.py``); only
the depth and the vocabulary rows held are cut, and each cut is printed.
Weights and data come from ``--seed``. Every check is printed on its own
line; the last line of stdout is ``{"ok": ..., "device": {...}}``. A failed
check or phase exits non-zero, and without a TPU nothing runs at all.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the barrier tier, one silo per
                                         # chip, against the fused tier
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# -- the cut: published widths, depth and vocabulary rows cut to one chip --
# 2 layers: the one-chip step peaks at 11.3 GB (XLA memory_analysis for a
# v5e, adding arguments and temporaries), 3 would take 13.7 GB, but the fused
# tier's 4-silo reference step of --four-chips (a (4, P) fp32 gradient stack)
# would need 16.3 GB at 3 layers against 13.3 GB at 2
N_LAYERS = 2
VOCAB_SHARE = 4       # one chip's rows of a 4-way vocab-parallel deployment
N_SILOS = 2           # the fused tier stacks (n_silos, P) fp32 gradients
TRAIN_STEPS = 3
BATCH, SEQ_LEN = 4, 512
# -- serving: 8 requests of 64..512 prompt tokens, 32 new tokens each ------
PROMPT_LENS = (64, 128, 192, 256, 320, 384, 448, 512)
SHARED_PREFIX = 256   # the four longest prompts open with one system prompt
NEW_TOKENS = 32
PAGE_SIZE, PREFILL_CHUNK = 16, 128
MAX_LEN = 576         # 512 + 32, rounded up to whole pages
SPEC_K, DRAFT_LAYERS = 4, 1

# One decode step of paged attention, Pallas kernel vs the jnp oracle, on the
# live pool. Both run the same online-softmax helpers on fp32 pages at the
# same matmul precision, so they differ by fp32 accumulation order in a
# softmax-weighted sum over at most 513 positions, ~1e-7 of max|V| (2.6e-8
# measured on a TPU v5e). 1e-5 leaves a wide margin, while rounding any
# operand to bfloat16 (2^-9) fails it, as does a wrong page, head or slot.
PAGED_RTOL = 1e-5
# Four chips, one SGD step: the aggregate sum_i clip(g_i) + noise is read
# back from the params. psum and the fused left fold add the same four terms
# in different orders, and the +-B*r mask terms (B = 8 sigma C) cancel only to
# fp32 rounding, ~1e-5; gradients come from two differently fused programs.
# The noise alone has std sigma*C = 1 per coordinate, a missing or doubled
# silo shifts coordinates by O(1).
AGG_ATOL = 1e-3
LOSS_RTOL = 1e-3      # per-silo losses from two compiled programs
FOUR_CHIP_LR = 1.0    # SGD: the update is the aggregate itself, / n_silos

PATH_KERNELS = {"dp_noise_tree", "dp_clip_accumulate", "dp_fused_noise_batch",
                "paged_attention", "paged_reset", "paged_rollback"}


def say(*parts):
    print(*parts, flush=True)


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        say(f"check {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip())
        if not ok:
            self.failed.append(name)
        return ok


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self, jax):
        self.seconds, self.programs, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"{self.programs} programs, {self.seconds:.1f} s backend "
                f"compile, persistent cache hits {self.hits} misses "
                f"{self.misses}")


def gib(n) -> str:
    return f"{n / 2**30:.2f} GiB"


def allocator_peak(jax) -> str:
    """Per-device ``peak_bytes_in_use``: arrays the runtime allocated. A
    compiled program's temporaries are not in it; :func:`program_memory`
    gives those."""
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return ", ".join(f"{d.id}: {gib(s.get('peak_bytes_in_use', 0))}"
                     for d, s in zip(jax.devices(), stats))


def program_memory(compiled) -> str:
    """XLA's per-device account of one compiled program."""
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    return (f"arguments {gib(m.argument_size_in_bytes)} + temporaries "
            f"{gib(m.temp_size_in_bytes)} + outputs "
            f"{gib(m.output_size_in_bytes)} - aliased "
            f"{gib(m.alias_size_in_bytes)} = {gib(peak)} per device")


def custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def model_config():
    from repro.configs.base import param_count
    from repro.configs.qwen25_3b import CONFIG

    cfg = dataclasses.replace(CONFIG, n_layers=N_LAYERS,
                              vocab_size=CONFIG.vocab_size // VOCAB_SHARE)
    say(f"model {CONFIG.name}: d_model {cfg.d_model}, {cfg.n_heads} query "
        f"and {cfg.n_kv_heads} kv heads of {cfg.d_head}, d_ff {cfg.d_ff}, "
        f"qkv_bias {cfg.qkv_bias}, rope_theta {cfg.rope_theta:g} (published)")
    say(f"cut: layers {CONFIG.n_layers} -> {cfg.n_layers} (depth)")
    say(f"cut: vocabulary rows {CONFIG.vocab_size} -> {cfg.vocab_size} (one "
        f"chip's share of a {VOCAB_SHARE}-way vocab-parallel deployment)")
    say(f"cut: params {param_count(CONFIG) / 1e6:.1f} M -> "
        f"{param_count(cfg) / 1e6:.1f} M")
    return cfg


def privacy(**kw):
    from repro.configs.base import PrivacyConfig

    return PrivacyConfig(enabled=True, sigma=1.0, clip_bound=1.0,
                         noise_lambda=0.5, mask_mode="pairwise", **kw)


# ---------------------------------------------------------------------------
# one chip


def train_phase(jax, cfg, seed, check, compiles):
    from repro.api import Session
    from repro.configs.base import OptimizerConfig

    say(f"train: fused tier, {N_SILOS} silos, batch {BATCH} x {SEQ_LEN} "
        f"tokens, {TRAIN_STEPS} steps, AdamW, sigma 1.0, lambda 0.5, "
        f"pairwise masks")
    sess = Session.from_config(
        cfg, privacy=privacy(sync_path="fused", n_silos=N_SILOS),
        optimizer=OptimizerConfig(name="adamw", lr=1e-4), seed=seed)
    t0 = time.perf_counter()
    res = sess.train(steps=TRAIN_STEPS, batch_size=BATCH, seq_len=SEQ_LEN,
                     log_every=0)
    say(f"train: {TRAIN_STEPS} steps in {time.perf_counter() - t0:.1f} s "
        f"wall, compile included ({compiles.line()})")
    losses = [m["loss"] for m in res.metrics]
    eps = res.final.get("epsilon")
    say(f"train: losses {losses}")
    say(f"train: epsilon {eps} at delta {sess.run_cfg.privacy.delta}")
    check("losses_finite", len(losses) == TRAIN_STEPS
          and bool(np.all(np.isfinite(losses))), f"{losses}")
    check("epsilon_reported", eps is not None and np.isfinite(eps),
          f"epsilon={eps}")
    say(f"train: device allocator peak {allocator_peak(jax)}")
    batch = sess.synthetic_batches(BATCH, SEQ_LEN)()
    # the program the steps ran, compiled again: a cache hit by now
    step = res.trainer._jit_step.lower(
        res.state, batch, jax.random.PRNGKey(seed + 1)).compile()
    say(f"train: step memory {program_memory(step)}")
    n = custom_calls(step)
    check("train_step_has_tpu_custom_call", n > 0, f"{n} in the train step")
    return sess.model, res.state.params


def make_requests(vocab, seed, tenant=None):
    from repro.runtime.server import Request

    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, SHARED_PREFIX)
    reqs = []
    for rid, n in enumerate(PROMPT_LENS):
        own = rng.integers(0, vocab, n)
        prompt = np.concatenate([prefix, own])[:n] if n > SHARED_PREFIX \
            else own
        reqs.append(Request(rid=rid, prompt=prompt.astype(np.int32),
                            max_new_tokens=NEW_TOKENS, tenant=tenant))
    return reqs


def probe_paged_attention(jax, srv, seed, check):
    """One decode step of paged attention on the live layer-0 pool: the
    Pallas kernel against the jnp oracle it is tested bit-equal to on CPU."""
    import jax.numpy as jnp
    from repro.kernels.paged_attention.paged_attention import \
        paged_attention_pallas
    from repro.kernels.paged_attention.ref import paged_attention_oracle

    k = srv.pool.pages["k_pages"][0]
    v = srv.pool.pages["v_pages"][0]
    tables = jnp.asarray(srv.pool.tables)
    q_start = jnp.asarray(srv.pool.lengths)
    n_valid = jnp.asarray([int(s is not None) for s in srv.slots], jnp.int32)
    cfg = srv.model.cfg
    q = jax.random.normal(jax.random.PRNGKey(seed + 7),
                          (srv.n_slots, 1, cfg.n_heads, k.shape[-1]),
                          jnp.float32)
    got = paged_attention_pallas(q, k, v, tables, q_start, n_valid)
    want = paged_attention_oracle(q, k, v, tables, q_start, n_valid)
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(v)))
    say(f"serve: paged attention pallas vs jnp on the live pool, slots at "
        f"{np.asarray(q_start).tolist()} tokens: max|diff| {err:.3e}, "
        f"max|V| {scale:.3e}, ratio {err / scale:.3e}")
    check("paged_attention_pallas_matches_jnp", err <= PAGED_RTOL * scale,
          f"ratio {err / scale:.3e} <= {PAGED_RTOL}")


def serve_phase(jax, model, params, seed, check):
    from repro.kernels.paged_attention.paged_attention import (
        paged_reset_pallas, paged_rollback_pallas)
    from repro.runtime.server import Request
    from repro.runtime.serving import ContinuousServer

    vocab = model.cfg.vocab_size
    say(f"serve: {len(PROMPT_LENS)} requests, prompts {PROMPT_LENS} tokens, "
        f"{NEW_TOKENS} new tokens each, page size {PAGE_SIZE}, prefill "
        f"chunk {PREFILL_CHUNK}")
    srv = ContinuousServer(model, params, max_batch=len(PROMPT_LENS),
                           max_len=MAX_LEN, page_size=PAGE_SIZE,
                           prefill_chunk=PREFILL_CHUNK)
    reqs = make_requests(vocab, seed)
    for r in reqs:
        srv.submit(r)
    t0 = time.perf_counter()
    probed = False
    while srv.queued or any(s is not None for s in srv.slots):
        srv.step()
        # every request admitted and past its prefill: decode-only tick
        if not probed and not srv.queued and all(
                s is None or s.pending is not None for s in srv.slots):
            probe_paged_attention(jax, srv, seed, check)
            probed = True
    check("paged_attention_probe_ran", probed)
    counts = [len(r.generated) for r in reqs]
    say(f"serve: {srv.stats.useful_tokens} tokens in {srv.clock} ticks, "
        f"{time.perf_counter() - t0:.1f} s wall, compile included; tokens "
        f"per request {counts}")
    check("every_request_served", counts == [NEW_TOKENS] * len(reqs),
          f"{counts}")

    say(f"serve: prefix sharing + speculative decoding (k={SPEC_K}, "
        f"{DRAFT_LAYERS}-layer early-exit draft) on the "
        f"{sum(n > SHARED_PREFIX for n in PROMPT_LENS)} prompts that share "
        f"a {SHARED_PREFIX}-token prefix")
    spec = ContinuousServer(model, params, max_batch=len(PROMPT_LENS),
                            max_len=MAX_LEN, page_size=PAGE_SIZE,
                            prefill_chunk=PREFILL_CHUNK, prefix_sharing=True,
                            speculative=True, spec_k=SPEC_K,
                            draft_layers=DRAFT_LAYERS)
    sreqs = [Request(rid=r.rid, prompt=r.prompt, max_new_tokens=NEW_TOKENS,
                     tenant="tenant-a")
             for r in reqs if len(r.prompt) > SHARED_PREFIX]
    spec.submit(sreqs[0])  # its prompt pages enter the prefix index first
    spec.run_until_drained()
    for r in sreqs[1:]:
        spec.submit(r)
    spec.run_until_drained()
    s = spec.stats
    scounts = [len(r.generated) for r in sreqs]
    say(f"serve: speculative pass {s.useful_tokens} tokens, shared prompt "
        f"tokens {s.shared_prompt_tokens}, proposed {s.spec_proposed}, "
        f"accepted {s.spec_accepted}; tokens per request {scounts}")
    check("every_speculative_request_served",
          scounts == [NEW_TOKENS] * len(sreqs), f"{scounts}")
    same = [r.generated == reqs[r.rid].generated for r in sreqs]
    say(f"serve: speculative output equals plain output token for token: "
        f"{same} (reported, not gating)")
    say(f"serve: device allocator peak {allocator_peak(jax)}")

    tables, lengths = srv.pool.tables, srv.pool.lengths
    n_valid = np.ones_like(lengths)
    row = tables[0]
    programs = {
        "decode": (srv._step_fn, params, np.zeros((len(lengths), 1), np.int32),
                   srv.pool.pages, tables, lengths, n_valid),
        "prefill_chunk": (srv._step_fn, params,
                          np.zeros((len(lengths), PREFILL_CHUNK), np.int32),
                          srv.pool.pages, tables, lengths, n_valid),
        "verify": (spec._verify_fn, params,
                   np.zeros((len(lengths), SPEC_K), np.int32),
                   spec.pool.pages, tables, lengths, n_valid),
        "draft_propose": (spec._propose_fn, spec.draft_params,
                          spec.pool.draft_pages, tables, lengths, lengths,
                          n_valid),
        "paged_reset": (paged_reset_pallas, srv.pool.pages["k_pages"],
                        srv.pool.pages["v_pages"], row),
        "paged_rollback": (paged_rollback_pallas, srv.pool.pages["k_pages"],
                           srv.pool.pages["v_pages"], row[:2],
                           np.asarray([1, 5], np.int32)),
    }
    for name, (fn, *args) in programs.items():
        n = custom_calls(fn.lower(*args).compile())
        check(f"{name}_has_tpu_custom_call", n > 0, f"{n}")


def one_chip(jax, args, check, compiles):
    from repro.kernels.dispatch import REGISTRY

    cfg = model_config()
    with REGISTRY.record() as resolved:
        model, params = train_phase(jax, cfg, args.seed, check, compiles)
        serve_phase(jax, model, params, args.seed, check)
    say(f"kernels: {{{', '.join(f'{k}: {sorted(v)}' for k, v in sorted(resolved.items()))}}}")
    bad = {k: v for k, v in resolved.items() if not v <= {"pallas", "packed"}}
    missing = PATH_KERNELS - set(resolved)
    check("kernels_resolved_pallas_or_packed", not bad and not missing,
          f"fell back: {bad or 'none'}, not reached: "
          f"{sorted(missing) or 'none'}")


# ---------------------------------------------------------------------------
# four chips: the barrier tier against the fused tier


def four_chips(jax, args, check, compiles):
    from repro.api import Session
    from repro.configs.base import OptimizerConfig

    n = len(jax.devices())
    if not check("four_devices", n == 4, f"{n} devices"):
        return
    cfg = model_config()
    opt = OptimizerConfig(name="sgd", lr=FOUR_CHIP_LR)
    say(f"barrier: (4,) data mesh, one silo per chip, one SGD step on batch "
        f"{n} x {SEQ_LEN}; the fused tier takes the same step on device 0")
    sess_b = Session.from_config(cfg, privacy=privacy(sync_path="barrier"),
                                 optimizer=opt, seed=args.seed)
    t0 = time.perf_counter()
    res_b = sess_b.train(steps=1, batch_size=n, seq_len=SEQ_LEN, log_every=0)
    say(f"barrier: step in {time.perf_counter() - t0:.1f} s wall, compile "
        f"included ({compiles.line()}); loss {res_b.final['loss']}")
    say(f"barrier: device allocator peak {allocator_peak(jax)}")
    batch = sess_b.synthetic_batches(n, SEQ_LEN)()
    with jax.set_mesh(res_b.trainer.mesh):
        compiled = res_b.trainer._jit_step.lower(
            res_b.state, batch, jax.random.PRNGKey(args.seed + 1)).compile()
    say(f"barrier: step memory {program_memory(compiled)}")
    hlo = compiled.as_text()
    kinds = {k: len(re.findall(rf"\b{k}(?:-start)?\(", hlo))
             for k in ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute",
                       "collective-broadcast")}
    # the gradient crosses chips once, as the psum of the packed buffer; the
    # only other all-reduce may carry the scalar loss and norm metrics
    reduces = [m.group(1) for m in re.finditer(
        r"= (\S+(?: \S+)*?) all-reduce(?:-start)?\(", hlo)]
    packed = [r for r in reduces if re.search(r"\[\d", r)]
    say(f"barrier: collectives in the compiled step {kinds}; all-reduce "
        f"results {reduces}")
    check("psum_only_collective",
          sum(kinds.values()) == kinds["all-reduce"] and len(packed) == 1,
          f"{len(packed)} non-scalar all-reduce: {packed}")
    tok = compiled.input_shardings[0][1]["tokens"]
    shard = tok.shard_shape(batch["tokens"].shape)
    devices = sorted(d.id for d in tok.device_set)
    say(f"barrier: batch sharding {tok}, shard {shard} on devices {devices}")
    check("one_silo_per_chip", devices == sorted(d.id for d in jax.devices())
          and shard == (1, SEQ_LEN), f"shard {shard} on {devices}")
    check("barrier_has_tpu_custom_call", "tpu_custom_call" in hlo)
    p_b = jax.device_get(res_b.state.params)
    loss_b = res_b.final["loss"]
    del res_b

    sess_f = Session.from_config(
        cfg, privacy=privacy(sync_path="fused", n_silos=n), optimizer=opt,
        seed=args.seed)
    res_f = sess_f.train(steps=1, batch_size=n, seq_len=SEQ_LEN, log_every=0)
    p_f = jax.device_get(res_f.state.params)
    loss_f = res_f.final["loss"]
    del res_f
    p0 = jax.device_get(sess_f.model.init(jax.random.PRNGKey(args.seed)))

    def agg(p):  # sum_i clip(g_i) + noise = n * (p0 - p) / lr under SGD
        return [(a.astype(np.float64) - b) * n / FOUR_CHIP_LR
                for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p))]

    diff = max(float(np.max(np.abs(a - b)))
               for a, b in zip(agg(p_b), agg(p_f)))
    size = max(float(np.max(np.abs(a))) for a in agg(p_f))
    say(f"barrier vs fused: max|aggregate diff| {diff:.3e} (aggregate "
        f"max|.| {size:.3e}); loss {loss_b} vs {loss_f}")
    check("barrier_matches_fused", diff <= AGG_ATOL,
          f"{diff:.3e} <= {AGG_ATOL}")
    check("barrier_loss_matches_fused",
          abs(loss_b - loss_f) <= LOSS_RTOL * abs(loss_f),
          f"{loss_b} vs {loss_f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights, the tokens and the requests")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the barrier tier on a (4,) data mesh, "
                         "one silo per chip, against the fused tier")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    compiles = CompileStats(jax)
    say(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}, compile cache {cache}")
    check = Checks()
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(jax, args, check, compiles)
    say(f"compile: {compiles.line()}")
    say(f"total: {time.perf_counter() - t0:.1f} s wall")
    ok = not check.failed
    if not ok:
        say(f"failed checks: {check.failed}")
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
