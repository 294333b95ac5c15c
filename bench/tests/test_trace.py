"""The trace reduction, on a small trace recorded on the CPU
(``data/cpu_trace.xplane.pb``, made by ``make_cpu_trace.py``) and on hand
intervals."""
from pathlib import Path

import pytest

from bench.lib import trace as tr

DATA = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"
CPU = dict(device_plane=r"^/host:CPU$", op_line=r"^tf_XLA")


def test_union_idle_and_overlap_by_hand():
    iv = [(0, 4), (2, 6), (8, 9), (12, 20)]
    assert tr.union_ns(iv, 0, 10) == 7          # [0,6] + [8,9]
    assert tr.union_ns(iv, 3, 15) == 3 + 1 + 3  # [3,6] [8,9] [12,15]
    assert tr.idle_gaps(iv, 0, 10) == [(6, 8), (9, 10)]
    assert tr.idle_gaps([], 0, 5) == [(0, 5)]
    assert tr.overlap_ns((5, 13), [(6, 8), (9, 10), (12, 30)]) == 2 + 1 + 1


def test_span_at_picks_the_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.tick", 10, 50),
             ("bench.fit", 20, 30)]
    assert tr.span_at(spans, 25) == "bench.fit"
    assert tr.span_at(spans, 40) == "bench.tick"
    assert tr.span_at(spans, 60) == "host"


@pytest.fixture(scope="module")
def cpu_trace():
    return tr.load(str(DATA), **CPU)


def test_recorded_trace_spans_and_window(cpu_trace):
    names = [n for n, _, _ in cpu_trace.spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.tick") == 3
    (lo, hi), = cpu_trace.spans_named("bench.window")
    assert (cpu_trace.lo, cpu_trace.hi) == (lo, hi)
    for s, e in cpu_trace.spans_named("bench.tick"):
        assert lo <= s < e <= hi


def test_recorded_trace_busy_matches_a_direct_sweep(cpu_trace):
    from jax.profiler import ProfileData

    raw = []
    for plane in ProfileData.from_file(str(DATA)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith("tf_XLA"):
                raw += [(e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.duration_ns > 0]
    lo, hi = cpu_trace.lo, cpu_trace.hi
    covered = set()
    step = 1000  # 1 us grid: the union by counting covered cells
    for s, e in raw:
        a, b = max(s, lo), min(e, hi)
        covered.update(range(int(a // step), int(-(-b // step))))
    busy = cpu_trace.busy_ns(0)
    assert busy > 0
    assert abs(busy - len(covered) * step) <= 2 * step * len(raw)
    gaps = tr.idle_gaps([(s, e) for _, s, e in cpu_trace.ops[0]], lo, hi)
    assert abs(busy + sum(e - s for s, e in gaps) - (hi - lo)) < 1e-3


def test_recorded_trace_each_tick_runs_the_matmul(cpu_trace):
    ops = cpu_trace.ops[0]
    for s, e in cpu_trace.spans_named("bench.tick"):
        assert any(n.startswith("dot_general") and s <= a < e
                   for n, a, _ in ops)
    assert cpu_trace.op_ns(0, r"^dot_general") > 0
