"""The benchmark harness: reads ``BENCHMARK.json``, finds the cell's
configuration, mix, limits and per-layer metric readers by name, checks the
device, runs the mix's driver, reduces the trace and prints the result line.

Everything that belongs to one configuration, mix or metric is a file of its
own, found by name:

* ``bench/configs/<config>.json``  sizes (Hugging Face ``config.json`` keys)
* ``bench/traffic/<mix>.json``     mix parameters; ``driver`` names the
  driver module under ``bench/lib`` (``train`` or ``serve``)
* ``bench/limits/<cell>.json``     the limits of the cell's compared numbers
  (a number the driver reads that has no limit there is printed, not
  compared)
* ``bench/metrics/<metric>.py``    a per-layer reader: ``read(run)`` returns
  a number, or None where it finds nothing to read
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

from bench.lib import model as model_mod

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class NoChip(RuntimeError):
    pass


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def find(kind: str, name: str, suffix: str, search) -> Path:
    for d in search:
        p = Path(d) / kind / f"{name}{suffix}"
        if p.is_file():
            return p
    raise FileNotFoundError(f"no {kind}/{name}{suffix} under {list(search)}")


def cell_e2e(spec: dict, workload: str) -> list:
    """The end-to-end metrics the cell reports: those that list it or list
    no cells."""
    return [m for m in spec["end_to_end"]
            if m.get("workloads") is None or workload in m["workloads"]]


def cell_per_layer(spec: dict, workload: str) -> list:
    """The per-layer metrics the cell reports: those that list it, or list
    no cells and move an end-to-end metric the cell reports."""
    e2e = {m["name"] for m in cell_e2e(spec, workload)}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in e2e)]


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def check_devices(chips: int):
    """The cell's devices; a CPU, too few chips or an unknown kind is an
    error before anything is loaded."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks_for(devs[0].device_kind)
    return devs[:chips]


def enable_compile_cache(root: Path) -> Path:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is cached, however quick its compile."""
    import jax

    path = root / ".jax_cache"
    path.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileStats:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (one listener per process)."""

    _inst = None

    def __init__(self):
        self.programs, self.seconds, self.hits, self.misses = 0, 0.0, 0, 0

    @classmethod
    def get(cls) -> "CompileStats":
        if cls._inst is None:
            import jax

            cls._inst = inst = cls()

            def duration(event, duration, **_):
                if event == "/jax/core/compile/backend_compile_duration":
                    inst.seconds += duration
                    inst.programs += 1

            def event(event, **_):
                if event == "/jax/compilation_cache/cache_hits":
                    inst.hits += 1
                elif event == "/jax/compilation_cache/cache_misses":
                    inst.misses += 1

            jax.monitoring.register_event_duration_secs_listener(duration)
            jax.monitoring.register_event_listener(event)
        return cls._inst


@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Context:
    """What a driver sees: the cell, its configuration and mix, and the
    harness's hooks for set-up, the window and freeing the device."""

    def __init__(self, *, workload, sizes, mix, seed, seconds, trace, chips,
                 devices, t_start, hooks=None, control=False, fault=None,
                 trace_dir=None):
        self.workload, self.sizes, self.mix = workload, sizes, mix
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.chips, self.devices = chips, devices
        self.t_start, self.hooks = t_start, hooks or {}
        self.control, self.fault = control, fault
        self.trace_dir = trace_dir
        self.setup_s = None
        self.memory_peak = 0
        self.compiles = CompileStats.get()
        self.window_compiles = 0
        self.xplane = None

    def hook(self, name, obj):
        fn = self.hooks.get(name)
        if fn is not None:
            fn(obj)

    def setup_done(self):
        self.setup_s = time.perf_counter() - self.t_start

    def span(self, name):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        import jax

        if self.setup_s is None:
            self.setup_done()
        before = self.compiles.programs
        win = Window()
        with contextlib.ExitStack() as stack:
            if self.trace:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                stack.enter_context(jax.profiler.trace(
                    str(self.trace_dir), profiler_options=opts))
                stack.enter_context(jax.profiler.TraceAnnotation(
                    "bench.window"))
            win.t0 = time.perf_counter()
            yield win
            win.t1 = time.perf_counter()
        self.window_compiles = self.compiles.programs - before
        if self.trace:
            from bench.lib import trace as trace_mod

            self.xplane = trace_mod.latest_xplane(str(self.trace_dir))

    def after_window(self):
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak = max(peaks, default=0)

    def free_device(self):
        import jax

        gc.collect()
        for a in jax.live_arrays():
            a.delete()


class Run:
    """What a per-layer reader sees."""

    def __init__(self, *, trace, records, sizes, peaks, chips, mix):
        self.trace, self.records, self.sizes = trace, records, sizes
        self.peaks, self.chips, self.mix = peaks, chips, mix


def load_reader(name: str, search):
    path = find("metrics", name, ".py", search)
    spec = importlib.util.spec_from_file_location(f"bench_metric_{len(name)}_"
                                                  + name.replace(".", "_")
                                                  .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def breakdown(tr, devs) -> dict:
    from bench.lib import trace as trace_mod

    d = devs[0]
    by_op: dict = {}
    for name, s, e in tr.ops.get(d, []):
        lo, hi = max(s, tr.lo), min(e, tr.hi)
        if hi > lo:
            by_op[name] = by_op.get(name, 0.0) + (hi - lo) * 1e-9
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = trace_mod.idle_gaps([(s, e) for _, s, e in tr.ops.get(d, [])],
                               tr.lo, tr.hi)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {"device_ops": [[n, v] for n, v in top],
            "idle_gaps": [[trace_mod.span_at(tr.spans, (a + b) / 2),
                           (b - a) * 1e-9] for a, b in gaps]}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_chip: bool = True, hooks=None,
             search=None, control: bool = False, fault=None,
             t_start=None, config_override=None, mix_override=None,
             keep_trace=None) -> dict:
    """One run of one cell; returns the result object the command prints."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    search = list(search or []) + [root / "bench"]
    cfg = config_override or load_json(find("configs", cell["config"], ".json",
                                            search))
    mix = mix_override or load_json(find("traffic", cell["traffic"], ".json",
                                         search))
    limits = load_json(find("limits", workload, ".json", search))["limits"]
    chips = int(cell["chips"])

    import jax

    if require_chip:
        devices = check_devices(chips)
        peaks = peaks_for(devices[0].device_kind)
    else:
        devices = jax.devices()[:chips]
        peaks = load_json(BENCH / "peaks.json")["TPU v5 lite"]
    if require_chip:
        enable_compile_cache(root)
    sizes = model_mod.sizes(cfg, cell["config"])
    ctx = Context(workload=workload, sizes=sizes, mix=mix,
                  seed=seed, seconds=seconds, trace=trace, chips=chips,
                  devices=devices, t_start=t_start, hooks=hooks,
                  control=control, fault=fault,
                  trace_dir=root / ".bench_trace" / workload)
    driver = importlib.import_module(f"bench.lib.{mix['driver']}")
    out = driver.run(ctx)

    # a control or a planted fault stands in the program's place and is
    # judged as the program would be
    stand_in = "control" if control else ("fault" if fault else None)
    judged = out[stand_in] if stand_in else out["readings"]
    checks = {name: {"value": value, "limit": limits[name]}
              for name, value in judged.items() if name in limits}
    uncompared = {name: value for name, value in judged.items()
                  if name not in limits}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values()) and out["failed"] == 0 \
        and ctx.window_compiles == 0

    metrics = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak}
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if trace:
        from bench.lib import trace as trace_mod

        tr = trace_mod.load(ctx.xplane)
        devs = sorted(tr.ops)[:chips]
        run = Run(trace=tr, records=out["records"], sizes=sizes, peaks=peaks,
                  chips=chips, mix=mix)
        for m in cell_per_layer(spec, workload):
            v = load_reader(m["name"], search).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if devs:
            device["busy_s"] = sum(tr.busy_ns(d) for d in devs) / len(devs) \
                * 1e-9
            result["breakdown"] = breakdown(tr, devs)
        device["window_s"] = tr.window_ns * 1e-9
        if keep_trace:
            shutil.copy(ctx.xplane, keep_trace)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        values = dict(out["metrics"], setup_s=ctx.setup_s)
        for m in cell_e2e(spec, workload):
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    result["_diag"] = {"setup_s": ctx.setup_s,
                       "window_compiles": ctx.window_compiles,
                       "compile": {"programs": ctx.compiles.programs,
                                   "seconds": ctx.compiles.seconds,
                                   "cache_hits": ctx.compiles.hits,
                                   "cache_misses": ctx.compiles.misses},
                       "records": {k: v for k, v in out["records"].items()
                                   if not isinstance(v, list)}}
    if uncompared:
        result["_diag"]["uncompared"] = uncompared
    if stand_in:
        result["_diag"]["stand_in"] = stand_in
        result["_diag"]["program"] = out["readings"]
    return result


def emit(result: dict) -> None:
    """Diagnostics and the compared numbers on stderr (the numbers last),
    then the result line on stdout with ``checks`` as its last key."""
    diag = result.pop("_diag", {})
    checks = result.pop("checks")
    print(json.dumps(diag, default=float), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None, t_start=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    emit(result)
    return 0
