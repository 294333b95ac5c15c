"""Serving driver: ``ContinuousServer.submit`` and ``ContinuousServer.step``
under the mix's open or closed loop, then the comparison of served tokens
with the plain reference.

Open loop: each request is due at its planned arrival and is submitted at
the first tick boundary past it; time to first token runs from when it was
due to when its first token reached the host (the end of the tick that made
it). After the window closes, ticks go on without new arrivals until every
request due in the window has its first token (at most ``DRAIN_S``).
Closed loop: the first ``concurrency`` requests are prefilled during set-up;
in the window a finished request is replaced at once.

Closed loop: after the window, ticks go on without new requests until
``check_requests`` requests have finished (at most ``DRAIN_S``).

Inter-token gaps are those whose two tokens both landed in the window.
``serve_tokens_per_s`` counts every token that landed in the window.

Comparison: a sample of finished requests drawn from the seed, the longest
among them; the reference runs once over each prompt with its served
tokens, and ``token_gap`` is the widest gap by which a served token's logit
lies below the reference's best at that position. With ``ctx.control``
the reference one precision lower stands in the program's place: at the
same positions, the gap of the token it puts first (``out["control"]``).
With ``ctx.fault == "token_altered"`` one sampled served token is altered
before the comparison (``out["fault"]``). The harness judges either.
"""
from __future__ import annotations

import time

import numpy as np

from bench.lib import reference, traffic, weights
from bench.lib.model import program_config

DRAIN_S = 60.0


def _p95(x) -> float:
    return float(np.percentile(np.asarray(x, np.float64), 95)) if len(x) \
        else float("nan")


def token_gaps(s, p, reqs, max_len: int, against=None) -> np.ndarray:
    """For each request, the reference's logits over prompt + served tokens;
    per served token, max logit minus the token's logit. ``against`` (a
    precision of ``reference.PRECISIONS``): read the gap of the token a
    reference at that precision puts first, at the same positions (the
    control)."""
    gaps = []
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])
        n0 = len(r.prompt)
        lg = reference.served_logits(s, p, seq, max_len)[n0 - 1:-1]
        toks = np.asarray(r.generated, np.int64)
        if against is not None:
            low = reference.served_logits(s, p, seq, max_len, against)
            toks = np.argmax(low[n0 - 1:-1], -1)
        gaps.append(lg.max(-1) - lg[np.arange(len(toks)), toks])
    return np.concatenate(gaps) if gaps else np.zeros((0,))


def gap_stats(g) -> dict:
    """How many served tokens are not the reference's first choice, and
    their mean gap over all compared tokens (diagnostics beside the widest
    gap)."""
    return {"flips": int(np.sum(g > 0)),
            "mean_gap": float(g.mean()) if g.size else float("nan")}


def run(ctx):
    from repro.api import Session
    from repro.runtime.server import Request
    from repro.runtime.serving import ContinuousServer

    mix, s = ctx.mix, ctx.sizes
    sc = mix["server"]
    model = Session.from_config(program_config(s),
                                seed=weights.seed31(ctx.seed)).model
    params = weights.make(s, ctx.seed)
    srv = ContinuousServer(model, params, max_batch=sc["max_batch"],
                           max_len=sc["max_len"], page_size=sc["page_size"],
                           prefill_chunk=sc["prefill_chunk"])
    calls = []
    step_fn = srv._step_fn

    def recorded(p, tokens, pages, tables, lengths, n_valid):
        calls.append((tokens.shape[1], np.array(n_valid), np.array(lengths)))
        return step_fn(p, tokens, pages, tables, lengths, n_valid)

    srv._step_fn = recorded
    ctx.hook("server", srv)

    # warm-up: one admission (page reset), a prefill chunk and a decode
    warm = Request(rid=-1, prompt=np.zeros(sc["prefill_chunk"] + 1, np.int32),
                   max_new_tokens=2)
    srv.submit(warm)
    srv.run_until_drained()
    calls.clear()

    closed = mix["loop"] == "closed"
    plan = traffic.closed_loop(mix, ctx.seed, s.vocab) if closed else \
        traffic.open_loop(mix, ctx.seconds, ctx.seed, s.vocab)
    reqs, seen, times = {}, {}, {}
    nxt = 0

    def submit(pl, due_abs):
        r = Request(rid=pl.rid, prompt=pl.prompt, max_new_tokens=pl.max_new,
                    tenant=pl.tenant)
        r.due = due_abs
        srv.submit(r)
        reqs[r.rid], seen[r.rid], times[r.rid] = r, 0, []
        return r

    def harvest(t):
        done = []
        for rid in list(seen):
            r = reqs[rid]
            n = len(r.generated)
            if n > seen[rid]:
                times[rid].extend([t] * (n - seen[rid]))
                seen[rid] = n
            if r.done:
                del seen[rid]
                done.append(r)
        return done

    if closed:
        for nxt in range(int(mix["concurrency"])):
            submit(plan[nxt], time.perf_counter())
        nxt += 1
        while srv.queued or any(sl is not None and sl.pending is None
                                for sl in srv.slots):
            srv.step()
            harvest(time.perf_counter())
        calls.clear()
    ctx.setup_done()

    ticks, late, queue = [], [], []
    with ctx.window() as win:
        t0 = win.t0
        while True:
            now = time.perf_counter()
            if now - t0 >= ctx.seconds:
                break
            while not closed and nxt < len(plan) and plan[nxt].due <= now - t0:
                submit(plan[nxt], t0 + plan[nxt].due)
                late.append(now - t0 - plan[nxt].due)
                nxt += 1
            if not srv.queued and all(sl is None for sl in srv.slots):
                if closed or nxt >= len(plan):
                    break
                with ctx.span("bench.wait"):
                    time.sleep(max(0.0, min(plan[nxt].due, ctx.seconds)
                                   - (time.perf_counter() - t0)))
                continue
            c0 = len(calls)
            with ctx.span("bench.tick"):
                srv.step()
            t = time.perf_counter()
            ticks.append((now, t, c0, len(calls)))
            queue.append((t - t0, srv.queued))
            for r in harvest(t):
                if closed and nxt < len(plan) and t - t0 < ctx.seconds:
                    submit(plan[nxt], t)
                    nxt += 1
        t_close = time.perf_counter()
    window_s = win.seconds
    ctx.after_window()
    # no new arrivals: in an open loop every request due in the window gets
    # its first token (or is counted failed after DRAIN_S); in a closed loop
    # ticks go on until enough requests have finished to compare
    due = [r for r in reqs.values() if not closed]
    need = min(int(mix["check_requests"]), len(reqs))

    def pending():
        if closed:
            return sum(r.done for r in reqs.values()) < need
        return any(not r.generated for r in due)

    while pending() and time.perf_counter() - t_close < DRAIN_S:
        srv.step()
        harvest(time.perf_counter())
    t_end = time.perf_counter()

    half = [(t, q) for t, q in queue if t >= window_s / 2]
    slope = float(np.polyfit(*zip(*half), 1)[0]) if len(half) > 2 else 0.0
    in_win = [t for ts in times.values() for t in ts if t0 < t <= t_close]
    gaps = [b - a for ts in times.values() for a, b in zip(ts, ts[1:])
            if a > t0 and b <= t_close]
    metrics = {"serve_tokens_per_s": len(in_win) / window_s,
               "serve_itl_p95_ms": 1e3 * _p95(gaps)}
    failed = 0
    if not closed:
        ttft = []
        for r in due:
            if r.generated:
                ttft.append(times[r.rid][0] - r.due)
            else:
                ttft.append(t_end - r.due)
                failed += 1
        metrics["serve_ttft_p95_ms"] = 1e3 * _p95(ttft)
    finished = [r for r in reqs.values() if r.done]
    out = {"metrics": metrics,
           "attempted": len(reqs),
           "failed": failed,
           "records": {"ticks": ticks, "calls": calls, "queue": queue,
                       "window_s": window_s,
                       "t0": t0, "t_close": t_close, "n_gaps": len(gaps),
                       "n_due": len(due), "tokens": len(in_win),
                       "late_p95_ms": 1e3 * _p95(late) if late else 0.0,
                       "late_max_ms": 1e3 * max(late, default=0.0),
                       "queue_end": queue[-1][1] if queue else 0,
                       "queue_slope_per_s": slope}}
    del srv, params, model
    ctx.free_device()

    # the comparison: a seeded sample of finished requests, the longest in it
    rng = np.random.default_rng(np.random.SeedSequence([int(ctx.seed), 7]))
    k = min(int(mix["check_requests"]), len(finished))
    longest = max(finished, key=lambda r: len(r.prompt) + len(r.generated),
                  default=None)
    pool = [r for r in finished if r is not longest]
    sample = ([longest] if longest is not None else []) + [
        pool[i] for i in rng.choice(len(pool), max(0, k - 1), replace=False)]
    p = weights.make(s, ctx.seed)
    g = token_gaps(s, p, sample, sc["max_len"])
    out["readings"] = {"token_gap": float(g.max()) if g.size else float("inf")}
    out["records"].update(compared_tokens=int(g.size),
                          **{f"{k}_program": v for k, v in gap_stats(g).items()})
    if ctx.control:
        gc = token_gaps(s, p, sample, sc["max_len"],
                        against=reference.CONTROL)
        out["control"] = {"token_gap": float(gc.max()) if gc.size
                          else float("inf")}
        out["records"].update(
            {f"{k}_control": v for k, v in gap_stats(gc).items()})
    if ctx.fault == "token_altered" and sample:
        r = sample[rng.integers(len(sample))]
        j = int(rng.integers(len(r.generated)))
        r.generated[j] = (r.generated[j] + 1) % s.vocab
        gf = token_gaps(s, p, sample, sc["max_len"])
        out["fault"] = {"token_gap": float(gf.max())}
    return out
