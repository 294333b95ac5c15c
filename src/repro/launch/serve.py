"""Confidential serving launcher: prefill + batched decode with the KV cache
(``python -m repro.launch.serve --arch <id> --tokens 32``).

Thin CLI over :meth:`repro.api.Session.serve`. ``--scheduler`` picks the
serving mode: ``direct`` (one lockstep batch, wall-clock timings), ``wave``
(length-bucketed static batching, the measured baseline) or ``continuous``
(paged KV cache with in-kernel slot recycling). Same trust boundaries as
training (attested components, encrypted assets); DP is a training-time
mechanism so the barrier is N/A here (DESIGN.md §5).

``--soak N`` runs a long Zipf-distributed trace (N requests) through the
continuous scheduler and reports ROLLING p99 latency over a sliding window
of completions — the figure that catches slot-recycling leaks and latency
drift a short drain never shows. The row is merged into ``BENCH_serve.json``
(read-modify-write: the wave/continuous comparison rows survive).
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.api import Session
from repro.launch.compile_cache import enable_compile_cache


def _rolling_p99(latencies, window: int = 64):
    """p99 over each sliding window of completions (completion order):
    max over windows = the worst sustained tail, not one outlier."""
    lat = np.asarray(latencies, np.float64)
    if len(lat) == 0:
        return [], None
    window = min(window, len(lat))
    p99s = [float(np.percentile(lat[i:i + window], 99))
            for i in range(0, len(lat) - window + 1, max(window // 4, 1))]
    return p99s, max(p99s)


def run_soak(sess: Session, n_requests: int, *, max_batch: int,
             page_size: int, prefill_chunk: int, window: int,
             out: str, seed: int = 0) -> dict:
    from repro.runtime.serving.load import zipf_requests

    requests = zipf_requests(n_requests, sess.cfg.vocab_size, seed=seed)
    res = sess.serve(scheduler="continuous", requests=requests,
                     max_batch=max_batch, max_len=512, page_size=page_size,
                     prefill_chunk=prefill_chunk)
    s = res.stats
    p99s, worst = _rolling_p99(s.latencies, window)
    row = {"requests": n_requests, "window": window,
           "useful_tokens": s.useful_tokens,
           "decode_steps": s.decode_steps,
           "utilization": round(s.utilization, 4),
           "p50_latency_steps": s.p50_latency_steps,
           "p99_latency_steps": s.p99_latency_steps,
           "rolling_p99_first": p99s[0] if p99s else None,
           "rolling_p99_last": p99s[-1] if p99s else None,
           "rolling_p99_worst": worst}
    # read-modify-write: the soak row joins the wave/continuous rows
    # instead of clobbering them
    bench = {}
    if os.path.exists(out):
        with open(out) as f:
            bench = json.load(f)
    bench["serve/soak"] = row
    with open(out, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="requests (scheduler modes) / batch rows (direct)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--scheduler", default="direct",
                    choices=["direct", "wave", "continuous"])
    ap.add_argument("--max-batch", type=int, default=8,
                    help="batch slots for the scheduler modes")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="continuous only: map same-tenant shared prompt "
                         "pages read-only (COW refcounts) instead of "
                         "re-prefilling them")
    ap.add_argument("--speculative", action="store_true",
                    help="continuous only: draft-propose/verify decoding "
                         "over a parallel draft page pool")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative tokens per tick (draft proposes k-1, "
                         "one chunk-shaped verify scores all k)")
    ap.add_argument("--draft-layers", type=int, default=None,
                    help="early-exit draft depth (first N target layers); "
                         "default: self-draft (all layers)")
    ap.add_argument("--tenant-weights", default=None, metavar="a=2,b=1",
                    help="continuous only: deficit-round-robin admission "
                         "weights per tenant (unlisted tenants weigh 1)")
    ap.add_argument("--soak", type=int, default=None, metavar="N",
                    help="soak mode: N Zipf requests through the continuous "
                         "scheduler, rolling p99 appended to --out")
    ap.add_argument("--window", type=int, default=64,
                    help="soak mode: completions per rolling-p99 window")
    ap.add_argument("--out", default="BENCH_serve.json",
                    help="soak mode: benchmark file to merge the row into")
    args = ap.parse_args()
    enable_compile_cache()

    sess = Session.from_config(args.arch, full=args.full)
    if not sess.cfg.causal:
        raise SystemExit(f"{sess.cfg.name} is encoder-only: no decode step")

    if args.soak is not None:
        row = run_soak(sess, args.soak, max_batch=args.max_batch,
                       page_size=args.page_size,
                       prefill_chunk=args.prefill_chunk,
                       window=args.window, out=args.out)
        print(f"arch={sess.cfg.name} soak={args.soak} "
              f"slots={args.max_batch} window={args.window}")
        print(f"useful tokens: {row['useful_tokens']} | utilization: "
              f"{row['utilization']:.3f}")
        print(f"rolling p99 (steps): first={row['rolling_p99_first']} "
              f"last={row['rolling_p99_last']} "
              f"worst={row['rolling_p99_worst']}")
        print(f"# merged serve/soak into {args.out}")
        return

    if args.scheduler == "direct":
        res = sess.serve(batch_size=args.batch, prompt_len=args.prompt_len,
                         max_new_tokens=args.tokens)
        print(f"arch={sess.cfg.name} batch={args.batch} "
              f"prompt={args.prompt_len} gen={args.tokens}")
        print(f"prefill: {res.prefill_s * 1e3:.1f} ms | decode: "
              f"{res.decode_s_per_token * 1e3:.2f} ms/token")
        print("first sequences:", res.tokens[:2, :8].tolist())
        return

    tenant_weights = None
    if args.tenant_weights:
        tenant_weights = {}
        for part in args.tenant_weights.split(","):
            name, _, w = part.partition("=")
            if not _ or not name:
                raise SystemExit(
                    f"--tenant-weights: bad entry {part!r} (want name=weight)")
            tenant_weights[name] = float(w)
    res = sess.serve(batch_size=args.batch, prompt_len=args.prompt_len,
                     max_new_tokens=args.tokens, scheduler=args.scheduler,
                     max_batch=args.max_batch,
                     max_len=args.prompt_len + args.tokens,
                     page_size=args.page_size,
                     prefill_chunk=args.prefill_chunk,
                     prefix_sharing=args.prefix_sharing,
                     speculative=args.speculative, spec_k=args.spec_k,
                     draft_layers=args.draft_layers,
                     tenant_weights=tenant_weights)
    s = res.stats
    print(f"arch={sess.cfg.name} scheduler={args.scheduler} "
          f"requests={args.batch} slots={args.max_batch}")
    print(f"useful tokens: {s.useful_tokens} | decode steps: "
          f"{s.decode_steps} | utilization: {s.utilization:.3f}")
    print(f"latency (steps): p50={s.p50_latency_steps:.0f} "
          f"p99={s.p99_latency_steps:.0f}")
    if args.prefix_sharing:
        print(f"shared prompt tokens: {s.shared_prompt_tokens}")
    if args.speculative:
        print(f"speculative: proposed={s.spec_proposed} "
              f"accepted={s.spec_accepted} "
              f"(acceptance {s.acceptance_rate:.2f})")
    print("first sequences:", res.tokens[:2, :8].tolist())


if __name__ == "__main__":
    main()
