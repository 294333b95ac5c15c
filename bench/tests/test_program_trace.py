"""The program's spans and scopes in a trace: the existing CPU trace loads
with the same benchmark spans and window as before, device ops get their
innermost program scope from the HLO modules stored in a recorded CPU trace
(``data/scope_trace.xplane.pb``, made by ``make_scope_trace.py``), self
time leaves out nested ops, and each new reader returns None where it has
nothing to read."""
from pathlib import Path

import numpy as np
import pytest

from bench.lib import harness
from bench.lib import program_trace as pt
from bench.lib import trace as tr
from bench.lib.model import Sizes

DATA = Path(__file__).parent / "data"
CPU = dict(device_plane=r"^/host:CPU$", op_line=r"^tf_XLA")
READERS = ("fwd_bwd_ms_per_step.train", "dp_engine_ms_per_step.train",
           "optimizer_ms_per_step.train", "sample_host_ms_per_tick.chat",
           "prefill_p95_ms.chat", "prefill_row_use.chat",
           "page_walk_use.chat", "page_walk_use.decode")
S = Sizes(name="tiny", n_layers=2, d_model=8, n_heads=4, n_kv_heads=2,
          d_head=2, d_ff=16, vocab=32, qkv_bias=True, tied=True,
          rope_theta=1e4, norm_eps=1e-6)


def reader(name):
    return harness.load_reader(name, [Path(__file__).parents[1]])


def run_with(trace=None, records=None, program=None):
    r = harness.Run(trace=trace, records=records or {}, sizes=S, peaks={},
                    chips=1, mix={})
    if program is not None:
        r.program_trace = program
    return r


@pytest.fixture(scope="module")
def scoped():
    return pt.load(str(DATA / "scope_trace.xplane.pb"), **CPU)


def test_existing_trace_keeps_its_bench_spans_and_window():
    old = tr.load(str(DATA / "cpu_trace.xplane.pb"), **CPU)
    new = pt.load(str(DATA / "cpu_trace.xplane.pb"), **CPU)
    assert [(n, s, e) for n, s, e, _ in new.spans
            if n.startswith("bench.")] == old.spans
    assert (new.lo, new.hi) == (old.lo, old.hi)
    assert new.busy(0) == [(s, e) for _, s, e in old.ops[0]]


def test_innermost_scope_matches_whole_segments():
    f = pt.innermost_scope
    assert f("jit(step)/train.fwd_bwd/jvp(loss)/dot_general") \
        == "train.fwd_bwd"
    assert f("jit(step)/transpose(jvp(train.fwd_bwd))/mul") == "train.fwd_bwd"
    assert f("jit(step)/train.fwd_bwd/vmap(dp.noise)/add") == "dp.noise"
    assert f("jit(step)/pretrain.fwd_bwd/add") is None
    assert f("jit(step)/my_dp.noise/add") is None
    assert f(None) is None and f("") is None


def test_self_time_by_hand():
    ops = {0: [("while", 0, 100, "train.fwd_bwd"), ("k", 10, 60, "dp.noise"),
               ("k2", 20, 30, "dp.clip"), ("f", 70, 90, None),
               ("g", 120, 150, "dp.noise")]}
    p = pt.ProgramTrace(spans=[], ops=ops, lo=0, hi=140)
    assert p.scope_ns(0) == {"train.fwd_bwd": 30, "dp.noise": 40 + 20,
                             "dp.clip": 10, None: 20}
    total = sum(p.scope_ns(0).values())
    assert total == tr.union_ns(p.busy(0), p.lo, p.hi)


def test_recorded_scopes_come_from_the_traces_hlo(scoped):
    ops = [o for o in scoped.ops[0] if o[3] is not None]
    by_scope = {}
    for op, _, _, scope in ops:
        by_scope.setdefault(scope, set()).add(op.split(".")[0])
    assert {"train.fwd_bwd", "dp.noise"} <= set(by_scope)
    assert "while" in by_scope["train.fwd_bwd"]
    assert by_scope["dp.noise"] >= {"dot_general", "wrapped_sine"}
    ns = scoped.scope_ns(0)
    assert ns["train.fwd_bwd"] > 0 and ns["dp.noise"] > 0


def test_recorded_loop_self_time_excludes_its_body(scoped):
    selfs = scoped.self_ns(0)
    loops = [t for t in selfs if t[0].startswith("while")]
    assert len(loops) == 2                      # one per call
    for _, scope, s, e, own in loops:
        assert scope == "train.fwd_bwd"
        body = [be - bs for _, sc, bs, be, _ in selfs
                if sc == "dp.noise" and s <= bs and be <= e]
        assert len(body) == 8                   # 4 iterations x 2 ops
        assert 0 <= own <= (e - s) - sum(body)
    assert sum(ns for _, sc, _, _, ns in selfs if sc == "dp.noise") \
        > 10 * sum(t[4] for t in loops)


def test_readers_read_a_hand_program_trace():
    spans = [("bench.window", 0, 1000, {}),
             ("serve.tick", 0, 400, {}), ("serve.tick", 500, 900, {}),
             ("serve.fetch", 100, 200, {}), ("serve.sample", 200, 300, {}),
             ("serve.dispatch", 50, 60, {"pages_live": 3, "pages_walked": 16,
                                         "rows_valid": 5, "rows_computed": 8}),
             ("serve.dispatch", 550, 560, {"pages_live": 1,
                                           "pages_walked": 16,
                                           "rows_valid": 0,
                                           "rows_computed": 0}),
             ("serve.first_token", 250, 251, {"prefill_ms": 10.0}),
             ("serve.first_token", 950, 951, {"prefill_ms": 30.0}),
             ("serve.first_token", 1500, 1501, {"prefill_ms": 99.0})]
    ops = {0: [("fusion", 0, 150, "paged.mlp"), ("fusion", 250, 260, None)]}
    p = pt.ProgramTrace(spans=spans, ops=ops, lo=0, hi=1000)
    r = run_with(trace=tr.Trace({}, [], 0, 1000), program=p)
    # idle inside fetch 150..200, inside sample 200..250 and 260..300
    assert np.isclose(reader("sample_host_ms_per_tick.chat").read(r),
                      (50 + 50 + 40) / 2 * 1e-6)
    assert np.isclose(reader("prefill_row_use.chat").read(r), 100 * 5 / 8)
    assert np.isclose(reader("page_walk_use.chat").read(r), 100 * 4 / 32)
    assert np.isclose(reader("prefill_p95_ms.chat").read(r),
                      np.percentile([10.0, 30.0], 95))
    steps = run_with(trace=tr.Trace({}, [], 0, 1000),
                     records={"steps": 2}, program=pt.ProgramTrace(
                         [], {0: [("a", 0, 4e6, "train.fwd_bwd"),
                                  ("b", 4e6, 6e6, "dp.noise"),
                                  ("c", 6e6, 7e6, "dp.pack"),
                                  ("d", 7e6, 8e6, "train.optimizer")]},
                         0, 1e7))
    assert reader("fwd_bwd_ms_per_step.train").read(steps) == 2.0
    assert reader("dp_engine_ms_per_step.train").read(steps) == 1.5
    assert reader("optimizer_ms_per_step.train").read(steps) == 0.5


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_trace_or_program_marks(name):
    read = reader(name).read
    assert read(run_with()) is None                      # no trace
    old = tr.load(str(DATA / "cpu_trace.xplane.pb"), **CPU)
    bare = pt.load(str(DATA / "cpu_trace.xplane.pb"), **CPU)
    assert read(run_with(trace=old, records={"steps": 3},
                         program=bare)) is None          # no program marks
    # a trace whose file is gone: nothing found, nothing raised
    assert read(run_with(trace=tr.Trace({}, [], -1.0, -2.0),
                         records={"steps": 3})) is None


def _pb(*fields):
    """Serialize ``(field, value)`` pairs: int -> varint, bytes/str ->
    length-delimited, list of ints -> packed."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            if isinstance(v, str):
                v = v.encode()
            elif isinstance(v, list):
                v = b"".join(varint(x) for x in v)
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_instruction_without_op_name_takes_its_called_computations_scope():
    md = lambda op: _pb((2, op))
    fused = _pb((1, "fused_computation.1"), (5, 7),
                (2, _pb((1, "mul.1"), (7, md("jit(s)/dp.noise/mul")))),
                (2, _pb((1, "add.1"), (7, md("jit(s)/dp.noise/add")))),
                (2, _pb((1, "p.1"))))
    entry = _pb((1, "main"), (5, 1),
                (2, _pb((1, "fusion.3"), (38, [7]))),
                (2, _pb((1, "copy.2"))),
                (2, _pb((1, "dot.4"), (7, md("jit(s)/train.fwd_bwd/dot")))))
    module = _pb((1, "jit_s"), (3, fused), (3, entry))
    stat_md = _pb((1, 9), (2, "Hlo Proto"))
    event_md = _pb((1, 1), (2, "jit_s(3)"),
                   (5, _pb((1, 9), (6, _pb((1, module))))))
    plane = _pb((1, 1), (2, "/host:metadata"), (4, _pb((1, 1), (2, event_md))),
                (5, _pb((1, 9), (2, stat_md))))
    names = pt.hlo_op_names(_pb((1, plane)))
    got = {k: pt.innermost_scope(v) for k, v in names["jit_s(3)"].items()}
    assert got == {"fusion.3": "dp.noise", "dot.4": "train.fwd_bwd",
                   "mul.1": "dp.noise", "add.1": "dp.noise"}
    assert set(names) == {"jit_s(3)"}
