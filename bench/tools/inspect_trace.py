#!/usr/bin/env python3
"""Run one cell with its trace on and write a plain summary of the trace:
every plane and line with its event count, the most frequent event names
per line with their total time, and a few events with their stats. For
reading a trace by hand before writing a reduction against it.

    python3 bench/tools/inspect_trace.py --workload <cell> --seed <n> \
        --seconds <s> --out trace_summary/<name>
"""
import argparse
import collections
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench.lib import harness  # noqa: E402


def summarize(path, out):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    with open(out, "w") as f:
        for plane in data.planes:
            f.write(f"PLANE {plane.name}\n")
            for line in plane.lines:
                evs = list(line.events)
                f.write(f"  LINE {line.name!r} events {len(evs)}\n")
                tot = collections.Counter()
                cnt = collections.Counter()
                for e in evs:
                    tot[e.name] += e.duration_ns
                    cnt[e.name] += 1
                for name, ns in tot.most_common(40):
                    f.write(f"    {ns / 1e6:12.3f} ms {cnt[name]:7d}x {name}\n")
                for e in evs[:3]:
                    f.write(f"    sample {e.name!r} start {e.start_ns} dur "
                            f"{e.duration_ns} stats {dict(e.stats)}\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    out = Path(a.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    keep = str(ROOT / ".bench_trace" / f"{a.workload}.kept.xplane.pb")
    Path(keep).parent.mkdir(exist_ok=True)
    res = harness.run_cell(a.workload, a.seed, a.seconds, True,
                           keep_trace=keep)
    harness.emit(res)
    summarize(keep, str(out) + ".txt")


if __name__ == "__main__":
    main()
