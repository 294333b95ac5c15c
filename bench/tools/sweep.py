#!/usr/bin/env python3
"""Find an open-loop cell's knee: run its mix at each rate and seed in one
process and report the tails and whether the backlog grows. The knee is the
highest rate at which, on every seed, the queue of requests waiting for a
slot does not grow over the window's second half (its least-squares slope,
in requests per second, at most ``--max-slope``) and every request due gets
a first token. Give a window long enough that its second half starts from
an occupied server: past the longest answer of the mix.

    python3 bench/tools/sweep.py --workload serve-chat.qwen2.5-3b \
        --rates 4.5,5,5.5,6 --seeds 11,12 --seconds 80 --out sweep.jsonl
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench.lib import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=80)
    ap.add_argument("--max-slope", type=float, default=0.05)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in spec["workloads"]}[a.workload]
    base = harness.load_json(ROOT / "bench" / "traffic"
                             / f"{cell['traffic']}.json")
    Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    held = {}
    for rate in [float(x) for x in a.rates.split(",")]:
        for seed in [int(x) for x in a.seeds.split(",")]:
            r = harness.run_cell(a.workload, seed, a.seconds, False,
                                 mix_override=dict(base, rate_per_s=rate))
            rec = r.pop("_diag")["records"]
            ok = rec["queue_slope_per_s"] <= a.max_slope and r["failed"] == 0
            held[rate] = held.get(rate, True) and ok
            line = {"rate_per_s": rate, "seed": seed, "holds": ok,
                    "correct": r["correct"], "failed": r["failed"],
                    "attempted": r["attempted"],
                    "metrics": {k: m["value"]
                                for k, m in r["metrics"].items()},
                    "records": rec}
            print(json.dumps(line), flush=True)
            with open(a.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    knee = max((r for r, ok in held.items() if ok), default=None)
    print(json.dumps({"knee_req_per_s": knee, "held": held}), flush=True)


if __name__ == "__main__":
    main()
