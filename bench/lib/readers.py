"""Reductions shared by the per-layer readers in ``bench/metrics``: device
idle share, a serving tick's least time at the chip's peaks, and a kernel's
byte-bound roofline share. Each returns None where the trace or the records
hold nothing to read, never 0 for a share."""
from __future__ import annotations

import math

from bench.lib import trace as trace_mod
from bench.lib.model import F32

# The DP engine's Pallas kernels, by the names their custom calls carry in a
# TPU trace (the function that calls ``pallas_call``, read from a trace by
# hand): the per-silo clipped sum, the fused clip + mask + noise kernel and
# the batched corrected-noise kernel.
DP_KERNELS = (r"^%(noise_batch_pallas|clip_accumulate|clip_mask_pallas|"
              r"clip_sum_pallas|zsmask_pallas|per_example_sumsq)(\.\d+)?$")


def devices(run) -> list:
    return sorted(run.trace.ops)[:run.chips] if run.trace else []


def idle_share(run):
    devs = devices(run)
    if not devs or run.trace.window_ns <= 0:
        return None
    busy = sum(run.trace.busy_ns(d) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / run.trace.window_ns)


def kernel_ns(run, pattern: str):
    """Device time of matching ops, averaged over the cell's chips."""
    devs = devices(run)
    if not devs:
        return None
    t = sum(run.trace.op_ns(d, pattern) for d in devs) / len(devs)
    return t if t > 0 else None


# ---------------------------------------------------------------------------
# serving: per program call, from the serving driver's record of
# (chunk width C, n_valid (B,), q_start (B,)) of every call into paged_step


def call_flops(s, n_valid, q_start) -> float:
    """Model FLOPs of the valid rows of one call: every layer matrix per
    valid token, causal attention over the cache and the chunk, and the head
    for each slot that produces a token."""
    f = 0.0
    for n, q in zip(n_valid, q_start):
        n, q = int(n), int(q)
        if n <= 0:
            continue
        f += 2.0 * s.n_layers * s.layer_matmul_params * n
        f += s.attn_flops_fwd(n * q + n * (n + 1) / 2)
        f += 2.0 * s.head_params
    return f


def live_kv_bytes(s, n_valid, q_start, page: int) -> float:
    """K/V bytes of the live pages every valid slot's queries must read,
    all layers."""
    toks = sum(math.ceil((int(q) + int(n)) / page) * page
               for n, q in zip(n_valid, q_start) if int(n) > 0)
    return float(toks * s.kv_bytes_per_token())


def call_bytes(s, n_valid, q_start, page: int) -> float:
    if not any(int(n) > 0 for n in n_valid):
        return 0.0
    return s.weight_bytes + live_kv_bytes(s, n_valid, q_start, page)


def least_time(run, c) -> float:
    _, n_valid, q_start = c
    s, pk = run.sizes, run.peaks
    page = run.mix["server"]["page_size"]
    return max(call_flops(s, n_valid, q_start) / pk["flops_per_s"],
               call_bytes(s, n_valid, q_start, page) / pk["hbm_bytes_per_s"])


def tick_share(run, prefill_only: bool = False):
    """Sum over ticks of the least time of the tick's calls at the chip's
    peaks, over the sum of the ticks' wall time (%). ``prefill_only``: the
    ticks that ran a prefill chunk, counting only that call's valid rows."""
    rec = run.records
    calls, num, den = rec.get("calls", []), 0.0, 0.0
    for t0, t1, a, b in rec.get("ticks", []):
        cs = calls[a:b]
        if prefill_only:
            cs = [c for c in cs if c[0] > 1 and c[1].sum() > 0]
            if not cs:
                continue
        num += sum(least_time(run, c) for c in cs)
        den += t1 - t0
    return 100.0 * num / den if den > 0 and num > 0 else None


def paged_kernel_bytes(run) -> float:
    """Bytes the paged-attention kernel must move over the window: live K/V
    pages of every valid slot plus its q rows read and out rows written,
    every layer of every call."""
    s = run.sizes
    page = run.mix["server"]["page_size"]
    q_row = F32 * s.n_heads * s.d_head
    total = 0.0
    for _, n_valid, q_start in run.records.get("calls", []):
        valid = int(sum(int(n) for n in n_valid))
        total += live_kv_bytes(s, n_valid, q_start, page) \
            + 2 * q_row * valid * s.n_layers
    return total


def roofline(bytes_moved: float, ns, bw: float):
    if not ns or bytes_moved <= 0:
        return None
    return 100.0 * bytes_moved / bw / (ns * 1e-9)


def host_idle_in_spans(run, span: str):
    """Mean over ``span`` spans of the time inside each during which the
    device is idle (ms)."""
    devs = devices(run)
    ticks = run.trace.spans_named(span) if run.trace else []
    if not devs or not ticks:
        return None
    d = devs[0]
    busy = [(s, e) for _, s, e in run.trace.ops[d]]
    gaps = trace_mod.idle_gaps(busy, run.trace.lo, run.trace.hi)
    idle = sum(trace_mod.overlap_ns(t, gaps) for t in ticks)
    return idle / len(ticks) * 1e-6
