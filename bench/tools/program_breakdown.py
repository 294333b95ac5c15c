#!/usr/bin/env python3
"""Run one cell with its trace on and break the traced window down by the
program's own spans and scopes (``bench/lib/program_trace.py``): device
self time per scope and the share of busy time under program scopes, the
top unscoped ops, the DP Pallas kernels' time inside ``dp.*`` scopes, idle
gaps labelled with the innermost program span, queue waits, and the cell's
end-to-end metrics as the traced run measured them (the result line of a
``--trace 1`` run holds only per-layer metrics).

    python3 bench/tools/program_breakdown.py --workload <cell> --seed <n> \\
        --seconds <s> --out <dir>/<name>.json [--keep <file>.gz]
    python3 bench/tools/program_breakdown.py --xplane <file> --steps <n>

``--keep`` writes the trace gzipped (dropped above 18 MiB compressed).
"""
import argparse
import collections
import gzip
import json
import re
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench.lib import harness, readers  # noqa: E402
from bench.lib import program_trace as pt_mod  # noqa: E402
from bench.lib import trace as trace_mod  # noqa: E402


def summarize(p, steps=None, ticks=None) -> dict:
    dev = sorted(p.ops)[0]
    selfs = p.self_ns(dev)
    busy = trace_mod.union_ns(p.busy(dev), p.lo, p.hi)
    by_scope = collections.Counter()
    unscoped = collections.Counter()
    for op, scope, _, _, ns in selfs:
        by_scope[scope or "(none)"] += ns
        if scope is None:
            unscoped[op] += ns
    fam = collections.Counter()
    for k, v in by_scope.items():
        fam[k.split(".")[0] if k != "(none)" else k] += v
    rx = re.compile(readers.DP_KERNELS)
    dp_all = sum(ns for op, _, _, _, ns in selfs if rx.search("%" + op))
    dp_in = sum(ns for op, sc, _, _, ns in selfs
                if rx.search("%" + op) and sc and sc.startswith("dp."))
    gaps = trace_mod.idle_gaps(p.busy(dev), p.lo, p.hi)
    spans3 = [(n, s, e) for n, s, e, _ in p.spans
              if n != "serve.first_token"]
    idle_by = collections.Counter()
    for a, b in gaps:
        idle_by[trace_mod.span_at(spans3, (a + b) / 2)] += b - a
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    waits = sorted(float(a["wait_ms"]) for a in
                   p.span_args("serve.first_token"))
    ticks = ticks or len(p.spans_named("serve.tick")) or None
    per = steps or ticks
    ms = (lambda ns: ns * 1e-6 / per) if per else (lambda ns: ns * 1e-6)
    out = {
        "window_s": (p.hi - p.lo) * 1e-9, "busy_s": busy * 1e-9,
        "per": "step" if steps else ("tick" if ticks else "window"),
        "scope_ms_per": {k: ms(v) for k, v in by_scope.most_common()},
        "family_share_of_busy_pct": {k: 100 * v / busy
                                     for k, v in fam.most_common()},
        "top_unscoped_ms_per": [[k, ms(v)] for k, v in
                                unscoped.most_common(8)],
        "dp_pallas_ms_per": ms(dp_all),
        "dp_pallas_in_dp_scopes_ms_per": ms(dp_in),
        "idle_ms_per_by_span": {k: ms(v) for k, v in idle_by.most_common()},
        "idle_gaps_top": [[trace_mod.span_at(spans3, (a + b) / 2),
                           (b - a) * 1e-9] for a, b in top_gaps],
        "n_ticks": len(p.spans_named("serve.tick")),
    }
    if waits:
        import numpy as np

        out["queue_wait_ms"] = {"p95": float(np.percentile(waits, 95)),
                                "max": waits[-1], "n": len(waits)}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--out")
    ap.add_argument("--keep")
    ap.add_argument("--xplane")
    ap.add_argument("--steps", type=int)
    a = ap.parse_args()
    if a.xplane:
        path = a.xplane
        if path.endswith(".gz"):
            raw = gzip.decompress(Path(path).read_bytes())
            path = str(ROOT / ".bench_trace" / "unzipped.xplane.pb")
            Path(path).parent.mkdir(exist_ok=True)
            Path(path).write_bytes(raw)
        print(json.dumps(summarize(pt_mod.load(path), steps=a.steps),
                         indent=1))
        return
    import importlib

    captured = {}
    for name in ("train", "serve"):
        mod = importlib.import_module(f"bench.lib.{name}")

        def wrapped(ctx, _run=mod.run):
            out = _run(ctx)
            captured.update(out["metrics"], setup_s=ctx.setup_s)
            return out
        mod.run = wrapped
    keep = ROOT / ".bench_trace" / f"{a.workload}.kept.xplane.pb"
    keep.parent.mkdir(exist_ok=True)
    res = harness.run_cell(a.workload, a.seed, a.seconds, True,
                           keep_trace=str(keep))
    steps = res["_diag"]["records"].get("steps")
    summary = {"workload": a.workload, "seed": a.seed,
               "end_to_end_traced": captured,
               "per_layer": {k: v["value"] for k, v in
                             res["metrics"].items()},
               "correct": res["correct"],
               "device": res["device"],
               "breakdown": res.get("breakdown"),
               "program": summarize(pt_mod.load(str(keep)), steps=steps)}
    harness.emit(res)
    text = json.dumps(summary, indent=1, default=float)
    print(text)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(text)
    if a.keep:
        with open(keep, "rb") as f, gzip.open(a.keep, "wb") as g:
            shutil.copyfileobj(f, g)
        if Path(a.keep).stat().st_size > 18 * 2**20:
            Path(a.keep).unlink()
    keep.unlink()


if __name__ == "__main__":
    main()
