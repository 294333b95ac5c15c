"""The program's own spans, span arguments and device scopes in a profiler
trace (``.xplane.pb``), beside the benchmark's.

``bench/lib/trace.py`` keeps the benchmark's ``bench.*`` spans. This module
reads the same file again and also keeps the program's host spans
(``serve.*``, ``train.*``: ``jax.profiler.TraceAnnotation`` in
``runtime/serving/scheduler.py`` and ``runtime/trainer.py``) with their
arguments, and gives each device op the innermost program scope
(``jax.named_scope``: ``train.*``, ``dp.*``, ``paged.*``) of its HLO
instruction. A TPU v5e op event carries no ``tf_op`` or ``long_name`` stat
(only its device offset and duration), so the scope is read from the
``op_name`` metadata of the instruction in the HLO modules the profiler
stores in the trace's ``/host:metadata`` plane (``Hlo Proto`` stats), keyed
by module and instruction name. An op's module is the enclosing event of its
plane's ``XLA Modules`` line (TPU), else its own ``hlo_module`` and
``program_id`` stats (CPU).

Device time is split by **self time**: an op's time less that of the ops
nested in it (a ``while`` op holds its body's ops), so every nanosecond of
busy time is counted once. A scope matches a whole path segment, or a name
inside a transform's parentheses, so the backward pass
(``transpose(jvp(...))``) of ops under ``train.fwd_bwd`` counts there.

With a program that has no such spans or scopes every reduction here finds
nothing, and the readers built on it return None.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from pathlib import Path

from bench.lib import trace as trace_mod

ROOT = Path(__file__).resolve().parents[2]
SPAN_PREFIXES = ("bench.", "serve.", "train.")
SCOPE = re.compile(r"(?:^|[/(])((?:train|dp|paged)\.[A-Za-z0-9_]+)(?=$|[/)])")


@dataclasses.dataclass
class ProgramTrace:
    spans: list    # [(name, start_ns, end_ns, args)], sorted by start
    ops: dict      # device -> [(op, start_ns, end_ns, scope or None)]
    lo: float
    hi: float

    def spans_named(self, name: str) -> list:
        """``(start, end)`` of the spans named ``name`` that start inside
        the window."""
        return [(s, e) for n, s, e, _ in self.spans
                if n == name and self.lo <= s < self.hi]

    def span_args(self, name: str) -> list:
        """The arguments of the spans named ``name`` that start inside the
        window."""
        return [a for n, s, _, a in self.spans
                if n == name and self.lo <= s < self.hi]

    def busy(self, dev) -> list:
        return [(s, e) for _, s, e, _ in self.ops.get(dev, [])]

    def self_ns(self, dev) -> list:
        """``(op, scope, start, end, self time)`` of every op of ``dev``
        inside the window: its time clipped to the window, less the time of
        the ops nested in it."""
        evs = []
        for op, s, e, scope in self.ops.get(dev, []):
            s, e = max(s, self.lo), min(e, self.hi)
            if e > s:
                evs.append([s, e, op, scope, 0.0])
        evs.sort(key=lambda v: (v[0], -v[1]))
        stack = []
        for ev in evs:
            while stack and stack[-1][1] <= ev[0]:
                stack.pop()
            if stack:
                stack[-1][4] += min(ev[1], stack[-1][1]) - ev[0]
            stack.append(ev)
        return [(op, scope, s, e, max(0.0, e - s - child))
                for s, e, op, scope, child in evs]

    def scope_ns(self, dev) -> dict:
        """Self time per innermost scope; unscoped ops under ``None``."""
        out: dict = {}
        for _, scope, _, _, ns in self.self_ns(dev):
            out[scope] = out.get(scope, 0.0) + ns
        return out


def innermost_scope(op_name):
    """The last program scope on an HLO ``op_name`` path, else None."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


# ---------------------------------------------------------------------------
# protobuf wire format, for the HLO modules in the /host:metadata plane
# (XSpace.planes = 1; XPlane.name = 2, event_metadata = 4 (map entry value =
# 2), stat_metadata = 5; XEventMetadata.name = 2, stats = 5; XStat
# metadata_id = 1, bytes_value = 6; HloProto.hlo_module = 1;
# HloModuleProto.computations = 3; HloComputationProto.instructions = 2,
# id = 5, root_id = 6; HloInstructionProto.name = 1, metadata = 7, id = 35,
# called_computation_ids = 38 (packed); OpMetadata.op_name = 2)


def _varint(b, i):
    r = sh = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << sh
        sh += 7
        if c < 0x80:
            return r, i


def _fields(b, lo, hi):
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = None, i + 8
        elif wt == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wt == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield f, v


def _sub(b, rng, field):
    return [v for f, v in _fields(b, *rng) if f == field]


def _text(b, rng, field):
    got = _sub(b, rng, field)
    return bytes(b[got[0][0]:got[0][1]]).decode() if got else None


def _ints(b, rng):
    """A packed repeated integer field."""
    out, i = [], rng[0]
    while i < rng[1]:
        v, i = _varint(b, i)
        out.append(v)
    return out


def _module_op_names(b, mod) -> dict:
    """``{instruction: op_name}`` of one HloModuleProto. An instruction with
    no op_name of its own (a fusion XLA built, say) takes the op_name of the
    most common program scope among the instructions of the computations it
    calls."""
    comps, ins_rows = {}, []
    for comp in _sub(b, mod, 3):
        cid = _sub(b, comp, 5)
        rows = []
        for ins in _sub(b, comp, 2):
            md = _sub(b, ins, 7)
            called = [c for r in _sub(b, ins, 38) for c in _ints(b, r)]
            rows.append((_text(b, ins, 1),
                         _text(b, md[0], 2) if md else None, called))
        if cid:
            comps[cid[0]] = rows
        ins_rows += rows

    def called_op_name(called, depth=0):
        votes: dict = {}
        for c in called:
            for _, op, sub in comps.get(c, []):
                if op is None and sub and depth < 2:
                    op = called_op_name(sub, depth + 1)
                if innermost_scope(op):
                    votes.setdefault(innermost_scope(op), []).append(op)
        if not votes:
            return None
        return max(votes.values(), key=len)[0]

    names = {}
    for name, op, called in ins_rows:
        if op is None and called:
            op = called_op_name(called)
        if op:
            names[name] = op
    return names


def hlo_op_names(data: bytes) -> dict:
    """``{module: {instruction: op_name}}`` from the HLO modules stored in a
    serialized XSpace, keyed by the metadata event's name: the module name
    with its program id (``jit_f(5)``), as a TPU's ``XLA Modules`` events
    name it and a CPU op's ``hlo_module`` and ``program_id`` stats give it."""
    b = memoryview(data)
    out: dict = {}
    for plane in _sub(b, (0, len(b)), 1):
        if _text(b, plane, 2) != "/host:metadata":
            continue
        hlo_stat = set()
        for entry in _sub(b, plane, 5):
            for sm in _sub(b, entry, 2):
                if _text(b, sm, 2) == "Hlo Proto":
                    hlo_stat.update(_sub(b, sm, 1))
        for entry in _sub(b, plane, 4):
            for em in _sub(b, entry, 2):
                key = _text(b, em, 2)
                for stat in _sub(b, em, 5):
                    ids = _sub(b, stat, 1)
                    if hlo_stat and ids and ids[0] not in hlo_stat:
                        continue
                    for proto in _sub(b, stat, 6):
                        for mod in _sub(b, proto, 1):
                            out.setdefault(key, {}).update(
                                _module_op_names(b, mod))
    return out


# ---------------------------------------------------------------------------


def load(path: str, *, device_plane: str = r"^/device:TPU:(\d+)$",
         op_line: str = r"^XLA Ops$", module_line: str = r"^XLA Modules$",
         window_span: str = "bench.window") -> ProgramTrace:
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    scopes = {m: {op: innermost_scope(n) for op, n in t.items()}
              for m, t in hlo_op_names(raw).items()}
    data = ProfileData.from_serialized_xspace(raw)
    dev_rx, line_rx = re.compile(device_plane), re.compile(op_line)
    mod_rx = re.compile(module_line)
    ops, spans = {}, []
    for plane in data.planes:
        m = dev_rx.search(plane.name)
        if m:
            dev = int(m.group(1)) if m.groups() else len(ops)
            evs = ops.setdefault(dev, [])
            lines = list(plane.lines)
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for ln in lines if mod_rx.search(ln.name)
                             for e in ln.events)
            for ln in lines:
                if line_rx.search(ln.name):
                    evs.extend(_ops(ln.events, modules, scopes))
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(SPAN_PREFIXES):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, dict(e.stats)))
    for evs in ops.values():
        evs.sort(key=lambda t: t[1])
    spans.sort(key=lambda t: t[1])
    win = [(s, e) for n, s, e, _ in spans if n == window_span]
    if win:
        lo, hi = win[0]
    else:
        allops = [t for evs in ops.values() for t in evs]
        lo = min((t[1] for t in allops), default=0.0)
        hi = max((t[2] for t in allops), default=0.0)
    return ProgramTrace(spans, ops, float(lo), float(hi))


def _ops(events, modules, scopes):
    """``(instruction, start, end, scope)`` of a line's op events."""
    starts = [m[0] for m in modules]
    for e in events:
        op = trace_mod.op_name(e.name).lstrip("%")
        s = e.start_ns
        table = {}
        if modules:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < modules[i][1]:
                table = scopes.get(modules[i][2], {})
        else:
            stats = dict(e.stats)
            mod = stats.get("hlo_module")
            if mod:
                table = scopes.get(f"{mod}({stats.get('program_id')})", {})
        yield (op, s, s + e.duration_ns, table.get(op))


def of(run):
    """The program trace of a harness run: the ``.xplane.pb`` the harness
    wrote under ``.bench_trace`` whose ``bench.window`` is the run's window
    (it is still on disk while the readers run). Found and loaded once per
    run and kept on it; None without a trace or without such a file."""
    if run.trace is None:
        return None
    if not hasattr(run, "program_trace"):
        run.program_trace = _find(run.trace.lo, run.trace.hi)
    return run.program_trace


def _find(lo, hi):
    files = glob.glob(str(ROOT / ".bench_trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        p = load(path)
        if (p.lo, p.hi) == (lo, hi):
            return p
    return None


# ---------------------------------------------------------------------------
# reductions for the per-layer readers; each returns None where the trace
# holds nothing to read


def _devices(run, pt) -> list:
    return sorted(pt.ops)[:run.chips] if pt else []


def scope_ms_per_step(run, match):
    """Device self time of ops whose innermost scope satisfies ``match``,
    averaged over the cell's chips, per training step (ms)."""
    pt = of(run)
    devs = _devices(run, pt)
    steps = run.records.get("steps")
    if not devs or not steps:
        return None
    ns = sum(v for d in devs for k, v in pt.scope_ns(d).items()
             if k is not None and match(k)) / len(devs)
    return ns / steps * 1e-6 if ns > 0 else None


def idle_in_spans_per_tick(run, names, tick: str = "serve.tick"):
    """Device-idle time inside the spans named in ``names``, over the
    window's ``tick`` spans (ms per tick)."""
    pt = of(run)
    devs = _devices(run, pt)
    ticks = pt.spans_named(tick) if pt else []
    if not devs or not ticks:
        return None
    gaps = trace_mod.idle_gaps(pt.busy(devs[0]), pt.lo, pt.hi)
    idle = sum(trace_mod.overlap_ns(sp, gaps)
               for n in names for sp in pt.spans_named(n))
    return idle / len(ticks) * 1e-6


def arg_share(run, span: str, num: str, den: str):
    """sum of argument ``num`` over sum of ``den`` (%), over the window's
    ``span`` spans."""
    pt = of(run)
    args = pt.span_args(span) if pt else []
    d = sum(float(a.get(den, 0)) for a in args)
    return 100.0 * sum(float(a.get(num, 0)) for a in args) / d if d > 0 \
        else None


def arg_p95(run, span: str, key: str):
    """p95 of argument ``key`` over the window's ``span`` spans."""
    import numpy as np

    pt = of(run)
    vals = [float(a[key]) for a in (pt.span_args(span) if pt else [])
            if key in a]
    return float(np.percentile(vals, 95)) if vals else None
