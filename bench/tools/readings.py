#!/usr/bin/env python3
"""The readings a cell's limits are set from: the program's compared numbers
over many seeds, and with ``--control`` the control's (the reference one
precision lower, in the program's place), with ``--fault`` a planted
fault's, all in one process so that set-up is paid once. With a control or
a fault, ``correct`` is the harness's verdict on that stand-in, judged
against the cell's limits exactly as the program is.

    python3 bench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--control] [--fault half_batch|token_altered] \
        --out readings/<name>.jsonl
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    with open(a.out, "a") as f:
        for seed in [int(x) for x in a.seeds.split(",")]:
            r = harness.run_cell(a.workload, seed, a.seconds, False,
                                 control=a.control, fault=a.fault)
            diag = r.pop("_diag")
            judged = {**{k: c["value"] for k, c in r["checks"].items()},
                      **diag.get("uncompared", {})}
            stand_in = diag.get("stand_in")
            line = {"seed": seed, "correct": r["correct"],
                    "program": diag["program"] if stand_in else judged,
                    "stand_in": stand_in,
                    stand_in or "judged": judged,
                    "metrics": {k: m["value"] for k, m in r["metrics"].items()},
                    "records": diag["records"],
                    "memory_peak_bytes": r["device"]["memory_peak_bytes"]}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()


if __name__ == "__main__":
    main()
