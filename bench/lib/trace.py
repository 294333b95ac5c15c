"""Reduction of a profiler trace (``.xplane.pb``) to device op intervals,
busy time, idle gaps and the benchmark's own host spans.

Device planes are those whose name matches ``device_plane`` (TPU:
``/device:TPU:<n>``); their op events are on the lines matching
``op_line`` (``XLA Ops``), each named by its HLO instruction name
(``%noise_batch_pallas.1``; a Pallas kernel's custom call carries the name
of the function that calls ``pallas_call``). Host spans are the ``jax.profiler.TraceAnnotation``
events whose name starts with ``span_prefix``. All times are nanoseconds on
the trace's one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re


@dataclasses.dataclass
class Trace:
    ops: dict          # device index -> [(name, start_ns, end_ns)], sorted
    spans: list        # [(name, start_ns, end_ns)], sorted by start
    lo: float          # traced window (the ``window`` span, else the ops')
    hi: float

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    def busy_ns(self, dev) -> float:
        return union_ns([(s, e) for _, s, e in self.ops[dev]], self.lo, self.hi)

    def op_ns(self, dev, pattern) -> float:
        """Device time of the ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(min(e, self.hi) - max(s, self.lo)
                   for n, s, e in self.ops[dev]
                   if rx.search(n) and e > self.lo and s < self.hi)

    def spans_named(self, name: str) -> list:
        return [(s, e) for n, s, e in self.spans if n == name]


def latest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str, *, device_plane: str = r"^/device:TPU:(\d+)$",
         op_line: str = r"^XLA Ops$", span_prefix: str = "bench.",
         window_span: str = "bench.window") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = {}, []
    dev_rx, line_rx = re.compile(device_plane), re.compile(op_line)
    for plane in data.planes:
        m = dev_rx.search(plane.name)
        if m:
            dev = int(m.group(1)) if m.groups() else len(ops)
            evs = ops.setdefault(dev, [])
            for line in plane.lines:
                if line_rx.search(line.name):
                    evs.extend((op_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(span_prefix):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    for evs in ops.values():
        evs.sort(key=lambda t: t[1])
    spans.sort(key=lambda t: t[1])
    win = [(s, e) for n, s, e in spans if n == window_span]
    if win:
        lo, hi = win[0]
    else:
        allops = [t for evs in ops.values() for t in evs]
        lo = min((s for _, s, _ in allops), default=0.0)
        hi = max((e for _, _, e in allops), default=0.0)
    return Trace(ops, spans, float(lo), float(hi))


def op_name(text: str) -> str:
    """The HLO instruction name of a TPU op event (its text up to `` = ``,
    which is followed by the shape and the operands)."""
    return text.split(" = ", 1)[0]


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """Maximal sub-intervals of ``[lo, hi]`` that no interval covers."""
    gaps, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def overlap_ns(a: tuple, intervals) -> float:
    """How much of interval ``a`` the sorted, disjoint ``intervals`` cover."""
    i = bisect.bisect_right([e for _, e in intervals], a[0])
    total = 0.0
    for s, e in intervals[i:]:
        if s >= a[1]:
            break
        total += max(0.0, min(a[1], e) - max(a[0], s))
    return total


def span_at(spans, t: float, skip=("bench.window",)) -> str:
    """Name of the innermost benchmark span open at ``t``, else ``host``."""
    best = None
    for n, s, e in spans:
        if s <= t < e and n not in skip and (best is None or s >= best[1]):
            best = (n, s)
    return best[0] if best else "host"
