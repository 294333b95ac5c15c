"""Zamba2 (the hybrid family) served through ``ContinuousServer``: chunked
prefill and decode against the plain reference (``zamba2_reference.py``),
the recurrent state's padding and idle-slot rules, leak-freedom of a
recycled slot's state, the two-group SSD kernel, and the refusals.

The config is the smoke cut of ``configs/zamba2_7b.py``: the published
pattern in small (mamba and hybrid layers in order, both shared blocks used
alternately, 2 groups, adapters, attention over concat(x, embedding))."""
import dataclasses
import glob
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import zamba2_reference
from repro.configs import get_smoke_config
from repro.kernels.mamba2 import ref as mref
from repro.kernels.mamba2.mamba2 import ssd_pallas
from repro.models import hybrid, mamba2
from repro.models.layers import rmsnorm
from repro.models.registry import build_model
from repro.runtime.server import Request
from repro.runtime.serving import ContinuousServer

# A float32 program and the highest-precision reference differ only by
# summation order (the chunked scan against the sequential one, the paged
# online softmax against a full one): about 1e-5 on logits up to about 4
# here. Serving in bfloat16 moves them by about 0.25, so 1e-3 passes the
# configuration's precision with 100x room and fails the one below it.
LOGIT_TOL = 1e-3


def _cfg():
    cfg = get_smoke_config("zamba2-7b")
    assert cfg.family == "hybrid" and cfg.n_mem_blocks == 2
    assert cfg.mamba_ngroups == 2 and cfg.adapter_rank > 0
    assert cfg.attn_inner == 2 * cfg.d_model
    return cfg


def _seeded(params, seed=0):
    """The init's tree with every norm scale, bias and dt bias moved off its
    constant, so each is exercised."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = []
    for (path, x), k in zip(flat, keys):
        name = jax.tree_util.keystr(path)
        z = jax.random.normal(k, x.shape, jnp.float32)
        if name.endswith("['scale']") or "gn_scale" in name:
            x = 1.0 + 0.1 * z
        elif "conv_b" in name or "['D']" in name:
            x = x + 0.1 * z
        elif "dt_bias" in name:
            x = -1.0 + 0.5 * z
        out.append(x.astype(jnp.float32))
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module")
def served():
    cfg = _cfg()
    model = build_model(cfg, compute_dtype=jnp.float32)
    params = _seeded(model.init(jax.random.PRNGKey(1)))
    return cfg, model, params


def _requests(cfg, lens=(13, 5, 20), new=6, seed=3):
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=rng.randint(0, cfg.vocab_size, n).tolist(),
                    max_new_tokens=new, tenant=f"t{i % 2}")
            for i, n in enumerate(lens)]


def _serve(model, params, reqs, **kw):
    kw = {"max_batch": 3, "max_len": 48, "page_size": 4, "prefill_chunk": 8,
          **kw}
    srv = ContinuousServer(model, params, trace_logits=True, **kw)
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    return srv


def _worst_gap(cfg, params, srv, reqs) -> float:
    worst = 0.0
    for r in reqs:
        seq = np.asarray(list(r.prompt) + r.generated[:-1], np.int32)
        ref = zamba2_reference.logits(params, cfg, seq)[len(r.prompt) - 1:]
        got = np.stack(srv.logit_trace[r.rid]).astype(np.float32)
        assert got.shape == ref.shape
        worst = max(worst, float(np.max(np.abs(got - ref))))
    return worst


def test_served_logits_match_reference(served):
    """Chunked prefill (chunks of 8 over prompts of 13, 5 and 20) and decode
    through the paged pool agree with the reference's full forward over
    prompt + served tokens, logit by logit; the same server in bfloat16
    does not."""
    cfg, model, params = served
    reqs = _requests(cfg)
    srv = _serve(model, params, reqs)
    assert all(r.done for r in reqs)
    assert srv.stats.state_resets == len(reqs)
    assert _worst_gap(cfg, params, srv, reqs) < LOGIT_TOL

    bf = build_model(cfg, compute_dtype=jnp.bfloat16)
    reqs_bf = _requests(cfg)
    srv_bf = _serve(bf, params, reqs_bf)
    assert _worst_gap(cfg, params, srv_bf, reqs_bf) > LOGIT_TOL


def _pool_step(model, params, cfg, tokens, n_valid, q_start, pool, tables):
    return jax.jit(model.paged_step)(params, jnp.asarray(tokens), pool,
                                     tables, jnp.asarray(q_start, jnp.int32),
                                     jnp.asarray(n_valid, jnp.int32))


def _fresh_pool(model, cfg, n_slots=3, seed=5):
    """A pool whose state slots hold noise, as a previous tenant left them."""
    pool = model.init_paged_cache(24, 4, n_slots=n_slots)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    pool["h"] = jax.random.normal(k1, pool["h"].shape, jnp.float32)
    pool["conv"] = jax.random.normal(k2, pool["conv"].shape, jnp.float32)
    return pool


def test_padding_and_idle_slots_leave_state(served):
    """Rows past ``n_valid`` and idle slots do not touch the recurrent
    state, bit for bit: an idle slot's state (prefill and decode) is what it
    was; a slot's state after a chunk with 5 of 8 rows valid does not depend
    on what the padded rows hold nor on what another slot computes; and it is
    the reference recurrence's state after those 5 tokens."""
    cfg, model, params = served
    tables = jnp.asarray(np.arange(24, dtype=np.int32).reshape(3, 8))
    rng = np.random.RandomState(7)
    toks = rng.randint(0, cfg.vocab_size, (3, 8)).astype(np.int32)
    zero = _fresh_pool(model, cfg)
    zero = {**zero, "h": zero["h"].at[:, 0].set(0),
            "conv": zero["conv"].at[:, 0].set(0)}

    def run(tokens, n_valid):
        pool = jax.tree.map(jnp.copy, zero)
        return _pool_step(model, params, cfg, tokens, n_valid, [0, 0, 0],
                          pool, tables)[1]

    a = run(toks, [5, 0, 0])
    other = toks.copy()
    other[0, 5:] = rng.randint(0, cfg.vocab_size, 3)   # other padding
    other[1] = rng.randint(0, cfg.vocab_size, 8)
    b = run(other, [5, 8, 0])                          # slot 1 busy
    for key in ("h", "conv"):
        np.testing.assert_array_equal(np.asarray(a[key][:, 0]),
                                      np.asarray(b[key][:, 0]))
        for slot in (1, 2):
            np.testing.assert_array_equal(np.asarray(a[key][:, slot]),
                                          np.asarray(zero[key][:, slot]))

    # decode: only rows with n_valid == 1 move
    dec = _pool_step(model, params, cfg, toks[:, :1], [1, 0, 0], [5, 0, 0],
                     jax.tree.map(jnp.copy, a), tables)[1]
    for key in ("h", "conv"):
        for slot in (1, 2):
            np.testing.assert_array_equal(np.asarray(dec[key][:, slot]),
                                          np.asarray(a[key][:, slot]))
        assert not np.array_equal(np.asarray(dec[key][:, 0]),
                                  np.asarray(a[key][:, 0]))

    # the state after 5 tokens: the sequential recurrence's, layer 0
    p0 = jax.tree.map(lambda t: t[0], params["mamba_layers"])
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks[0, :5]][None]
        y = rmsnorm(p0["ln"], x, cfg.norm_eps)
        st = {"h": jnp.zeros_like(zero["h"][0, :1]),
              "conv": jnp.zeros_like(zero["conv"][0, :1])}
        _, want = mamba2.mamba2_apply(p0["mamba"], y, cfg, st)
        _, scan_in, _ = mamba2.ssm_in(p0["mamba"], y, cfg)
        _, h_seq = mref.ssd_sequential(*scan_in, st["h"])
    np.testing.assert_allclose(np.asarray(a["h"][0, 0]), np.asarray(h_seq[0]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(a["conv"][0, 0]),
                               np.asarray(want["conv"][0]), atol=1e-6)


def test_recycled_slot_leaks_no_state(served):
    """One slot, two tenants in turn: tenant B's logits after A used the
    slot are bit-equal to B's on a fresh server. Without the admission reset
    of the state (the pool's hook removed) they are not — the check sees a
    leak."""
    cfg, model, params = served
    rng = np.random.RandomState(11)
    a = Request(rid=0, prompt=rng.randint(0, cfg.vocab_size, 17).tolist(),
                max_new_tokens=5, tenant="A")
    b_prompt = rng.randint(0, cfg.vocab_size, 9).tolist()
    mk_b = lambda: Request(rid=1, prompt=list(b_prompt), max_new_tokens=5,
                           tenant="B")
    recycled = _serve(model, params, [a, mk_b()], max_batch=1)
    fresh = _serve(model, params, [mk_b()], max_batch=1)
    assert recycled.stats.state_resets == 2
    got = np.stack(recycled.logit_trace[1])
    np.testing.assert_array_equal(got, np.stack(fresh.logit_trace[1]))

    leaky = ContinuousServer(model, params, max_batch=1, max_len=48,
                             page_size=4, prefill_chunk=8, trace_logits=True)
    leaky.pool._reset_state = None
    leaky.submit(Request(rid=0, prompt=list(a.prompt), max_new_tokens=5,
                         tenant="A"))
    leaky.submit(mk_b())
    leaky.run_until_drained()
    assert not np.array_equal(np.stack(leaky.logit_trace[1]), got)


@pytest.mark.parametrize("chunk", [64, 256])
def test_ssd_pallas_two_groups_matches_ref(chunk):
    """The Pallas SSD kernel (interpret mode) with B/C in 2 groups, picked
    per head by its index map, against the sequential oracle."""
    Bsz, S, nh, P, N, G = 2, 256, 4, 16, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(chunk), 6)
    xh = jax.random.normal(ks[0], (Bsz, S, nh, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bsz, S, nh))) * 0.2
    la = -jnp.exp(jax.random.normal(ks[2], (nh,))) * dt
    Bc = jax.random.normal(ks[3], (Bsz, S, G, N)) * 0.5
    Cc = jax.random.normal(ks[4], (Bsz, S, G, N)) * 0.5
    h0 = jax.random.normal(ks[5], (Bsz, nh, P, N)) * 0.1
    with jax.default_matmul_precision("highest"):
        y_ref, h_ref = mref.ssd_sequential(xh, dt, la, Bc, Cc, h0)
        y, h = ssd_pallas(xh, dt, la, Bc, Cc, h0, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=1e-4,
                               rtol=1e-4)
    # the groups are not interchangeable: a swap moves the output
    y_sw, _ = ssd_pallas(xh, dt, la, Bc[:, :, ::-1], Cc[:, :, ::-1], h0,
                         chunk=chunk, interpret=True)
    assert float(jnp.max(jnp.abs(y_sw - y_ref))) > 1e-2


def test_paged_step_carries_device_scopes(served):
    cfg, model, params = served
    srv = ContinuousServer(model, params, max_batch=2, max_len=32,
                           page_size=4, prefill_chunk=8)
    pool = srv.pool
    args = (params, np.zeros((2, 8), np.int32), pool.pages, pool.tables,
            pool.lengths, np.zeros((2,), np.int32))
    text = jax.jit(model.paged_step).lower(*args).compile().as_text()
    for scope in ("paged.ssm_in", "paged.ssd", "paged.ssm_out",
                  "paged.shared", "paged.qkv", "paged.kv_write",
                  "paged.attention", "paged.head"):
        assert f"/{scope}/" in text, scope


def test_admission_span_carries_state_resets(served):
    """``serve.admit`` carries the tick's increment of
    ``ServerStats.state_resets``: one per slot admitted."""
    cfg, model, params = served
    srv = ContinuousServer(model, params, max_batch=2, max_len=32,
                           page_size=4, prefill_chunk=8)
    for i in range(2):
        srv.submit(_requests(cfg, lens=(6,), new=3, seed=i)[0])
    srv.step()                                  # compile outside the trace
    srv.submit(_requests(cfg, lens=(5,), new=3, seed=9)[0])
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d, profiler_options=opts):
            while srv.stats.state_resets < 3:
                srv.step()
        data = jax.profiler.ProfileData.from_file(
            glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0])
    resets = [dict(e.stats).get("state_resets") for p in data.planes
              for ln in p.lines for e in ln.events if e.name == "serve.admit"]
    assert sum(resets) == 1 and set(resets) <= {0, 1}


@pytest.mark.parametrize("option", ["prefix_sharing", "speculative"])
def test_state_snapshot_options_refused(served, option):
    _, model, params = served
    with pytest.raises(ValueError, match="recurrent state"):
        ContinuousServer(model, params, max_batch=2, max_len=32,
                         page_size=4, prefill_chunk=8, **{option: True})


def test_layer_plan_follows_hybrid_ids():
    from repro.configs.zamba2_7b import CONFIG
    cut = dataclasses.replace(CONFIG, n_layers=12, hybrid_layer_ids=(6, 11))
    assert hybrid.plan(cut) == [("mamba", 0, 6), ("hybrid", 6, 0),
                                ("mamba", 7, 11), ("hybrid", 11, 1)]
    full = hybrid.plan(CONFIG)
    assert [k for kind, _, k in full if kind == "hybrid"] == list(range(13))
    assert sum(b - a for kind, a, b in full if kind == "mamba") == 81 - 13


@pytest.mark.parametrize("chunk,q_start,n_valid", [
    (16, [0, 5, 30], [16, 9, 0]),     # page-aligned, straddling, idle
    (16, [3, 16, 31], [13, 16, 1]),   # ragged tails, a slot's last row
    (1, [0, 17, 31], [1, 1, 0]),      # decode rows and an idle slot
])
def test_page_write_matches_element_write(chunk, q_start, n_valid):
    """``paged_kv_write_pages`` (the hybrid step's K/V write) leaves the
    pool bit-equal to ``paged_kv_write`` (the dense step's), padded rows and
    idle slots writing nothing."""
    from repro.models import attention as attn

    N, H, P, D, B = 12, 2, 8, 4, 3
    key = jax.random.split(jax.random.PRNGKey(3), 4)
    kp, vp = (jax.random.normal(k, (N, H, P, D)) for k in key[:2])
    k, v = (jax.random.normal(kk, (B, chunk, H, D)) for kk in key[2:])
    tables = jnp.asarray(np.random.default_rng(0).permutation(N)
                         .reshape(B, N // B), jnp.int32)
    args = (k, v, tables, jnp.asarray(q_start, jnp.int32),
            jnp.asarray(n_valid, jnp.int32))
    want = attn.paged_kv_write(kp, vp, *args)
    got = attn.paged_kv_write_pages(kp, vp, *args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
