"""The program's own measurement: ``ServerStats`` row and page counters and
``Request`` host-clock stamps against hand-computed values, the profiler
spans of a serving tick and of a training step with their nesting, the
device scopes in the compiled programs, and ``step_time_s`` taken from the
drained flush window."""
import glob
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import Session
from repro.configs import get_smoke_config
from repro.configs.base import OptimizerConfig, PrivacyConfig
from repro.models.registry import build_model
from repro.runtime.server import Request
from repro.runtime.serving import ContinuousServer


@pytest.fixture(scope="module")
def served():
    cfg = get_smoke_config("qwen2.5-3b")
    model = build_model(cfg, compute_dtype=jnp.float32)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _server(served, **kw):
    _, model, params = served
    return ContinuousServer(model, params, max_batch=2, max_len=32,
                            page_size=4, prefill_chunk=4, **kw)


def _prompt(cfg, n, seed):
    return np.random.RandomState(seed).randint(0, cfg.vocab_size, n).tolist()


def test_server_counts_rows_and_pages_by_hand(served):
    """Two requests, chunk 4, page 4, 2 slots x 8 table pages. A: prompt 6,
    3 tokens; B: prompt 3, 2 tokens.
    tick 1  prefill A[0:4] B[0:3]   rows 7/8  live 1+1  (B's 1st token)
    tick 2  decode B at 3           live 1              (B done)
            prefill A[4:6]          rows 2/8  live 2    (A's 1st token)
    tick 3  decode A at 6           live 2
    tick 4  decode A at 7           live 2              (A done)
    The kernel walks a valid slot's live pages in whole blocks of 8 (the
    whole table here): six slot-calls walk 8 pages each."""
    cfg = served[0]
    srv = _server(served)
    srv.submit(Request(rid=0, prompt=_prompt(cfg, 6, 1), max_new_tokens=3))
    srv.submit(Request(rid=1, prompt=_prompt(cfg, 3, 2), max_new_tokens=2))
    srv.run_until_drained()
    st = srv.stats
    assert srv.clock == 4
    assert (st.prefill_rows_valid, st.prefill_rows_computed) == (9, 16)
    assert (st.pages_live, st.pages_walked) == (9, 48)


def test_pages_walked_follow_the_kernel_blocks(served):
    """One mixed tick over 4 slots of a 64-page table (page 4): A decodes at
    6 tokens, B prefills its second 8-row chunk (16 tokens), C is admitted
    and prefills its first (8 tokens), slot 3 is empty. The kernel walks
    each valid slot's live pages in whole blocks of ``block_pages`` pages,
    for the decode call's rows and the chunk call's; the idle slot and the
    slots a call does not advance walk nothing."""
    from repro.kernels.paged_attention.paged_attention import block_pages
    cfg, model, params = served
    srv = ContinuousServer(model, params, max_batch=4, max_len=256,
                           page_size=4, prefill_chunk=8)
    srv.submit(Request(rid=0, prompt=_prompt(cfg, 5, 0), max_new_tokens=8))
    srv.submit(Request(rid=1, prompt=_prompt(cfg, 30, 1), max_new_tokens=8))
    srv.step()                      # A and B prefill their first chunk
    srv.step()                      # A decodes, B prefills its second
    srv.submit(Request(rid=2, prompt=_prompt(cfg, 8, 2), max_new_tokens=8))
    st = srv.stats
    live0, walked0 = st.pages_live, st.pages_walked
    srv.step()
    pool = srv.pool.pages["k_pages"]
    G, D, item = cfg.n_heads // cfg.n_kv_heads, pool.shape[-1], \
        pool.dtype.itemsize
    k_dec = block_pages(G, D, item, 4, 64)
    k_pre = block_pages(G * 8, D, item, 4, 64)
    assert 1 < k_pre <= k_dec < 64
    # decode: A at 6 + 1 tokens; prefill: B 16 + 8, C 0 + 8
    live = 2 + (6 + 2)
    walked = k_dec + 2 * k_pre
    assert (st.pages_live - live0, st.pages_walked - walked0) == (live, walked)
    assert st.pages_live <= st.pages_walked


def test_request_stamps_are_ordered(served):
    cfg = served[0]
    srv = _server(served)
    reqs = [Request(rid=i, prompt=_prompt(cfg, 3 + 4 * i, i),
                    max_new_tokens=3) for i in range(4)]
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first_token
    # two slots: the last two requests wait for a slot
    assert reqs[3].t_admit > reqs[0].t_first_token


def _profile(fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d, profiler_options=opts):
            fn()
        path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
        data = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in data.planes for ln in p.lines for e in ln.events
            if e.name.startswith(("serve.", "train."))]


def _inside(span, outer_spans):
    _, s, e, _ = span
    return any(a <= s and e <= b for _, a, b, _ in outer_spans)


def test_serving_tick_spans_nest(served):
    cfg = served[0]
    srv = _server(served)
    srv.submit(Request(rid=0, prompt=_prompt(cfg, 3, 0), max_new_tokens=4))
    srv.step()                      # A prefilled, decodes next tick
    srv.submit(Request(rid=1, prompt=_prompt(cfg, 3, 1), max_new_tokens=4))
    spans = _profile(srv.step)      # admit B, decode A, prefill B
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    assert len(by["serve.tick"]) == 1
    for name in ("serve.admit", "serve.decode", "serve.prefill"):
        assert len(by[name]) == 1 and _inside(by[name][0], by["serve.tick"])
    phases = by["serve.decode"] + by["serve.prefill"]
    for name in ("serve.prepare", "serve.dispatch", "serve.fetch",
                 "serve.sample"):
        assert len(by[name]) == 2, name
        assert all(_inside(sp, phases) for sp in by[name])
    for phase in phases:
        inner = [n for n, s, e, _ in spans
                 if phase[1] <= s and e <= phase[2] and n != phase[0]
                 and n != "serve.first_token"]
        assert inner == ["serve.prepare", "serve.dispatch", "serve.fetch",
                         "serve.sample"]
    # the calls' counter increments ride on the dispatch spans
    args = [a for _, _, _, a in sorted(by["serve.dispatch"],
                                       key=lambda t: t[1])]
    assert args[0] == {"pages_live": 1, "pages_walked": 8,
                       "rows_valid": 0, "rows_computed": 0}
    assert args[1] == {"pages_live": 1, "pages_walked": 8,
                       "rows_valid": 3, "rows_computed": 8}
    mark, = by["serve.first_token"]
    assert _inside(mark, by["serve.sample"])
    assert mark[3]["prefill_ms"] >= 0 and mark[3]["wait_ms"] >= 0


def _trainer(steps, **kw):
    sess = Session.from_config(
        "qwen2.5-3b", privacy=PrivacyConfig(enabled=True, sigma=0.5,
                                            n_silos=2),
        optimizer=OptimizerConfig(lr=1e-3))
    tr = sess.trainer(total_steps=steps, checkpoint_every=10**9,
                      log_every=0, batch_size=2, seq_len=16, **kw)
    return sess, tr


def test_training_step_spans():
    sess, tr = _trainer(1)
    # the first fit compiles; each fit runs total_steps from step 0
    state, _ = tr.fit(sess.init_state(), jax.random.PRNGKey(0))
    spans = _profile(lambda: jax.block_until_ready(
        tr.fit(state, jax.random.PRNGKey(0))[0]))
    names = [n for n, *_ in spans]
    for name in ("train.next_batch", "train.dispatch", "train.ledger",
                 "train.flush", "train.checkpoint"):
        assert names.count(name) == 1, name
    flush, = [sp for sp in spans if sp[0] == "train.flush"]
    assert _inside(flush, [sp for sp in spans
                           if sp[0] == "train.checkpoint"])
    order = [n for n, *_ in sorted(spans, key=lambda t: t[1])
             if n != "train.flush"]
    assert order == ["train.next_batch", "train.dispatch", "train.ledger",
                     "train.checkpoint"]


def test_step_time_is_the_flush_windows_per_step_time():
    sess, tr = _trainer(3)
    t0 = time.time()
    tr.fit(sess.init_state(), jax.random.PRNGKey(0))
    wall = time.time() - t0
    times = {e["step_time_s"] for e in tr.metrics_log}
    assert len(tr.metrics_log) == 3 and len(times) == 1
    per_step, = times
    assert 0 < 3 * per_step <= wall


def test_train_step_carries_device_scopes():
    sess, tr = _trainer(1)
    state = sess.init_state()
    batch = tr.next_batch()
    text = tr._jit_step.lower(state, batch, jax.random.PRNGKey(0)) \
        .compile().as_text()
    for scope in ("train.fwd_bwd", "train.optimizer", "dp.pack", "dp.norms",
                  "dp.clip", "dp.mask", "dp.noise", "dp.unpack"):
        assert f"/{scope}/" in text, scope


def test_paged_step_carries_device_scopes(served):
    srv = _server(served)
    pool = srv.pool
    args = (srv.params, np.zeros((2, 4), np.int32), pool.pages,
            pool.tables, pool.lengths, np.zeros((2,), np.int32))
    text = jax.jit(srv.model.paged_step).lower(*args).compile().as_text()
    for scope in ("paged.qkv", "paged.kv_write", "paged.attention",
                  "paged.mlp", "paged.head"):
        assert f"/{scope}/" in text, scope
