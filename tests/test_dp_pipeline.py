"""The single DP-aggregation engine (core/dp_pipeline.py): four-tier parity
on a fixed seed, zero-sum masking over partial participation sets, silo
dropout/rejoin with the noise-correction invariants, and the elastic trainer
wiring.

The four execution tiers:
  * fused  — vmap shim over ``DPPipeline.run_central`` (distributed/steps.py)
  * scan   — silo-serial shim over the engine's tree stages (per-leaf noise
             on its FSDP-sharded accumulator, a different stream family)
  * barrier— shard_map shim psumming ``silo_contribution`` (subprocess: needs
             a multi-device mesh)
  * wire   — TEE component protocol invoking the same stages per message
"""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (MeshConfig, OptimizerConfig, PrivacyConfig,
                                RunConfig, SHAPES)
from repro.configs.paper_models import MNIST_MLP3
from repro.core import barrier as barrier_mod
from repro.core import flatbuf
from repro.core.dp_pipeline import DPPipeline, reduce_contributions
from repro.core.noise_correction import NoiseState, corrected_noise, init_state
from repro.data.synthetic import synthetic_mnist
from repro.distributed import steps as steps_mod
from repro.models.registry import Model
from repro.models.small import build_small_model

ROOT = Path(__file__).resolve().parents[1]
N = 4
SIGMA = 0.5


def as_model(sm):
    return Model(cfg=None, init=sm.init, loss=sm.loss, init_cache=None,
                 prefill=None, decode_step=None)


def setup(sigma=SIGMA, lam=0.0, silo_mode="vmap"):
    sm = build_small_model(MNIST_MLP3)
    model = as_model(sm)
    priv = PrivacyConfig(enabled=True, sigma=sigma, clip_bound=1.0,
                         clip_mode="per_silo", noise_lambda=lam,
                         n_silos=N, silo_mode=silo_mode)
    train, _ = synthetic_mnist(n_train=128, n_test=16)
    batch = {"x": jnp.asarray(train.x[:32]), "y": jnp.asarray(train.y[:32])}
    params = model.init(jax.random.PRNGKey(0))
    keys = barrier_mod.step_keys(jax.random.PRNGKey(9),
                                 jnp.zeros((), jnp.int32))
    return model, priv, params, batch, keys


def manual_aggregate(model, params, batch, keys, active, sigma_c=SIGMA,
                     state=None, lam=0.0):
    """Ground truth: sum of the active silos' clipped grads + the engine's
    exact per-silo noise streams over the active set."""
    from repro.core import clipping
    from repro.kernels.dp_fused import ref as fref

    layout = flatbuf.layout_of(params)
    total = jnp.zeros((layout.total,), jnp.float32)
    for i in range(N):
        if not bool(active[i]):
            continue
        sl = {k: v[i * 8:(i + 1) * 8] for k, v in batch.items()}
        g = jax.grad(model.loss)(params, sl)
        g, _ = clipping.clip_tree(g, 1.0)
        total = total + flatbuf.pack(layout, g)
    k = float(np.sum(np.asarray(active)))
    s = sigma_c / np.sqrt(k)
    state = state or init_state(jax.random.PRNGKey(0), n_silos=N)
    pa = np.asarray(state.prev_active) if state.prev_active is not None \
        else np.ones(N, bool)
    hp = float(np.asarray(state.has_prev))
    idx = jnp.arange(layout.total, dtype=jnp.uint32)
    for i in range(N):
        if not bool(active[i]):
            continue
        total = total + s * fref._stream(keys.key_xi, idx, jnp.uint32(i))
        if lam > 0.0 and hp and pa[i]:
            s_prev = sigma_c / np.sqrt(max(float(pa.sum()), 1.0))
            total = total - lam * s_prev * fref._stream(
                state.prev_key, idx, jnp.uint32(i))
    return flatbuf.unpack(layout, total, dtype=jnp.float32)


def manual_scan_aggregate(model, params, batch, keys, active, state, lam,
                          sigma_c=SIGMA):
    """Ground truth for the scan tier: the active silos' clipped grads plus
    the per-leaf corrected noise it draws on its accumulator."""
    from repro.core import clipping

    total = None
    for i in range(N):
        if not bool(active[i]):
            continue
        sl = {k: v[i * 8:(i + 1) * 8] for k, v in batch.items()}
        g, _ = clipping.clip_tree(jax.grad(model.loss)(params, sl), 1.0)
        total = g if total is None else jax.tree.map(jnp.add, total, g)
    noise, _ = corrected_noise(total, keys.key_xi, state, sigma_c, lam)
    return jax.tree.map(lambda g, n: g.astype(jnp.float32) + n, total, noise)


def primed_state():
    """A step-t state whose step t-1 had every silo contributing, so the
    lambda-correction terms are live."""
    return NoiseState(prev_key=jnp.array([7, 8], jnp.uint32),
                      has_prev=jnp.ones((), jnp.bool_),
                      prev_active=jnp.ones((N,), jnp.bool_))


def max_err(a_tree, b_tree):
    return max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
               for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree)))


# ---------------------------------------------------------------------------
# four-tier parity (fused / scan / wire in-process; barrier in a subprocess
# on a real 4-device mesh below)


@pytest.mark.parametrize("lam", [0.0, 0.7])
def test_fused_scan_wire_parity_all_active(lam):
    """The fused and wire tiers run the same packed engine -> the same
    aggregate; the scan tier adds its per-leaf noise to the same clipped
    sum, with the lambda-correction live when ``lam`` > 0."""
    model, priv, params, batch, keys = setup(lam=lam)
    ns = primed_state()

    fused, loss_f, _, _, _ = steps_mod._fused_grads(
        model, priv, params, batch, N, keys, ns, jnp.float32(1.0),
        keys.key_clip)

    scan, loss_s, _, _, _ = steps_mod._fused_grads_scan(
        model, priv, params, batch, N, keys, ns, jnp.float32(1.0),
        keys.key_clip)

    # wire tier: per-silo silo_contribution + updater-order reduce
    layout = flatbuf.layout_of(params)
    pipe = DPPipeline(priv, layout, N)
    active = pipe.full_active()
    contribs = []
    for i in range(N):
        sl = {k: v[i * 8:(i + 1) * 8] for k, v in batch.items()}
        g = jax.grad(model.loss)(params, sl)
        scale = pipe.clip_scale(pipe.norm_tree(g), 1.0)
        contribs.append(pipe.finalize(pipe.silo_contribution(
            g, i, scale, active, keys, ns, 1.0)))
    wire = reduce_contributions(contribs)

    all_active = np.ones(N, bool)
    manual = manual_aggregate(model, params, batch, keys, all_active,
                              state=ns, lam=lam)
    assert max_err(fused, manual) < 2e-4
    assert max_err(wire, manual) < 2e-4
    manual_scan = manual_scan_aggregate(model, params, batch, keys,
                                        all_active, ns, lam)
    assert max_err(scan, manual_scan) < 2e-4
    assert max_err(fused, wire) < 2e-4
    np.testing.assert_allclose(float(loss_f), float(loss_s), rtol=1e-5)


def test_noise_construction_bit_identical_across_tiers():
    """On a zero gradient the fused tier's post-reduce noise accumulation is
    bit-identical to the wire tier's sequential contribution sum: same
    streams, same silo order, same fp association. ``mask_scale=0`` zeroes
    the r-terms exactly, so each wire contribution is exactly its noise
    share."""
    import dataclasses

    model, priv, params, batch, keys = setup(lam=0.7)
    priv = dataclasses.replace(priv, mask_scale=0.0)
    layout = flatbuf.layout_of(params)
    pipe = DPPipeline(priv, layout, N)
    ns = NoiseState(prev_key=jnp.array([7, 8], jnp.uint32),
                    has_prev=jnp.ones((), jnp.bool_),
                    prev_active=jnp.ones((N,), jnp.bool_))
    active = jnp.array([True, False, True, True])
    fused_noise = pipe.corrected_noise_packed(
        jnp.zeros((layout.total,), jnp.float32), keys, ns, 1.0, active)
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    total = None
    for i in range(N):
        c = pipe.silo_contribution(zeros, i, 1.0, active, keys, ns, 1.0)
        total = c if total is None else total + c
    np.testing.assert_array_equal(np.asarray(total), np.asarray(fused_noise))


def test_noise_construction_bit_identical_many_silos():
    """The same wire-vs-central bitwise contract at the many-silo scale
    (n=44 exercises the batched kernel's chunked fold), with distinct
    participation sets at t and t-1."""
    import dataclasses

    n = 44
    priv = PrivacyConfig(enabled=True, sigma=0.5, clip_bound=1.0,
                         clip_mode="per_silo", noise_lambda=0.7, n_silos=n,
                         silo_mode="vmap")
    priv = dataclasses.replace(priv, mask_scale=0.0)
    t = {"w": jnp.zeros((2048,), jnp.float32)}
    layout = flatbuf.layout_of(t)
    pipe = DPPipeline(priv, layout, n)
    keys = barrier_mod.step_keys(jax.random.PRNGKey(5),
                                 jnp.zeros((), jnp.int32))
    act = np.ones(n, bool)
    act[3::7] = False
    prev = np.ones(n, bool)
    prev[5::9] = False
    ns = NoiseState(prev_key=jnp.array([7, 8], jnp.uint32),
                    has_prev=jnp.ones((), jnp.bool_),
                    prev_active=jnp.asarray(prev))
    active = jnp.asarray(act)
    fused_noise = pipe.corrected_noise_packed(
        jnp.zeros((layout.total,), jnp.float32), keys, ns, 1.0, active)
    zeros = jax.tree.map(jnp.zeros_like, t)
    total = None
    for i in range(n):
        c = pipe.silo_contribution(zeros, i, 1.0, active, keys, ns, 1.0)
        total = c if total is None else total + c
    np.testing.assert_array_equal(np.asarray(total), np.asarray(fused_noise))


def test_parity_with_dynamic_clipping_and_correction():
    """Two steps with lambda-correction: fused and wire agree including the
    regenerated -lam*xi_{t-1} term."""
    model, priv, params, batch, keys = setup(lam=0.7)
    keys2 = barrier_mod.step_keys(jax.random.PRNGKey(9),
                                  jnp.ones((), jnp.int32))
    ns0 = init_state(jax.random.PRNGKey(0), n_silos=N)

    _, _, _, ns1, _ = steps_mod._fused_grads(
        model, priv, params, batch, N, keys, ns0, jnp.float32(1.0),
        keys.key_clip)
    fused2, _, _, _, _ = steps_mod._fused_grads(
        model, priv, params, batch, N, keys2, ns1, jnp.float32(1.0),
        keys2.key_clip)

    manual2 = manual_aggregate(model, params, batch, keys2,
                               np.ones(N, bool), state=jax.device_get(ns1),
                               lam=0.7)
    assert max_err(fused2, manual2) < 2e-4


# ---------------------------------------------------------------------------
# dropout: k < n active silos


@pytest.mark.parametrize("lam", [0.0, 0.7])
def test_dropout_aggregate_equals_k_silo_ground_truth(lam):
    """With active = [1,0,1,1] the aggregate must equal the 3-silo ground
    truth: dropped silos contribute no gradient, no mask, no noise share, and
    the noise std re-normalizes to exactly sigma*C. The scan tier's per-leaf
    noise is one stream at full sigma*C whatever the participation set."""
    model, priv, params, batch, keys = setup(lam=lam)
    ns = primed_state()
    active_np = np.array([True, False, True, True])
    active = jnp.asarray(active_np)

    fused, loss, _, _, _ = steps_mod._fused_grads(
        model, priv, params, batch, N, keys, ns, jnp.float32(1.0),
        keys.key_clip, active=active)
    manual = manual_aggregate(model, params, batch, keys, active_np,
                              state=ns, lam=lam)
    assert max_err(fused, manual) < 2e-4

    scan, _, _, _, _ = steps_mod._fused_grads_scan(
        model, priv, params, batch, N, keys, ns, jnp.float32(1.0),
        keys.key_clip, active=active)
    manual_scan = manual_scan_aggregate(model, params, batch, keys,
                                        active_np, ns, lam)
    assert max_err(scan, manual_scan) < 2e-4


def test_dropout_masks_still_sum_to_zero():
    """Sum of the active silos' zero-sum masks == the pure noise sum: the
    pairwise r-terms telescope over the ring of *active* silos."""
    model, priv, params, batch, keys = setup()
    layout = flatbuf.layout_of(params)
    pipe = DPPipeline(priv, layout, N)
    ns = init_state(jax.random.PRNGKey(0), n_silos=N)
    active = jnp.array([True, False, True, True])
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    total = None
    for i in range(N):
        c = pipe.silo_contribution(zeros, i, 1.0, active, keys, ns, 1.0)
        total = c if total is None else total + c
    noise_only = pipe.corrected_noise_packed(
        jnp.zeros((layout.total,), jnp.float32), keys, ns, 1.0, active)
    # masks cancel to fp rounding of the +-B*r pairs (B = mask_scale*sigma*C)
    np.testing.assert_allclose(np.asarray(total), np.asarray(noise_only),
                               atol=1e-5)
    # and each active contribution is wide-spread (property 2 intact)
    c0 = np.asarray(pipe.silo_contribution(zeros, 0, 1.0, active, keys, ns,
                                           1.0))
    assert c0.std() > 1.0  # B = 8*sigma*C = 4 >> 0


def test_dropout_noise_scale_renormalizes():
    """k active streams at sigma_c/sqrt(k) -> aggregate noise std sigma_c
    for every k."""
    priv = PrivacyConfig(enabled=True, sigma=3.0, clip_bound=1.0, n_silos=N)
    t = {"w": jnp.zeros((16384,), jnp.float32)}
    layout = flatbuf.layout_of(t)
    pipe = DPPipeline(priv, layout, N)
    keys = barrier_mod.step_keys(jax.random.PRNGKey(3),
                                 jnp.zeros((), jnp.int32))
    ns = init_state(jax.random.PRNGKey(0), n_silos=N)
    for active in (jnp.ones((N,), jnp.bool_),
                   jnp.array([True, False, True, False]),
                   jnp.array([False, False, True, False])):
        noise = pipe.corrected_noise_packed(
            jnp.zeros((layout.total,), jnp.float32), keys, ns, 1.0, active)
        std = float(np.std(np.asarray(noise)))
        assert abs(std - 3.0) / 3.0 < 0.08, (np.asarray(active), std)


def test_drop_and_rejoin_carries_correction_state():
    """Step 1 all active; step 2 silo 1 drops (its correction share leaves
    with it); step 3 it rejoins. Every step must match the engine's declared
    semantics: correction applies to active(t) & active(t-1) silos at the
    t-1 stream scale."""
    model, priv, params, batch, keys1 = setup(lam=0.7)
    schedule = [np.ones(N, bool),
                np.array([True, False, True, True]),
                np.ones(N, bool)]
    ns = init_state(jax.random.PRNGKey(0), n_silos=N)
    state_host = jax.device_get(ns)
    for t, active_np in enumerate(schedule):
        keys = barrier_mod.step_keys(jax.random.PRNGKey(9),
                                     jnp.asarray(t, jnp.int32))
        fused, _, _, new_ns, _ = steps_mod._fused_grads(
            model, priv, params, batch, N, keys, ns, jnp.float32(1.0),
            keys.key_clip, active=jnp.asarray(active_np))
        manual = manual_aggregate(model, params, batch, keys, active_np,
                                  state=state_host, lam=0.7)
        assert max_err(fused, manual) < 2e-4, f"step {t}"
        ns = new_ns
        state_host = jax.device_get(new_ns)
        np.testing.assert_array_equal(np.asarray(state_host.prev_active),
                                      active_np)


# ---------------------------------------------------------------------------
# barrier tier on a real mesh (subprocess: 4 host-platform devices)

BARRIER_PARITY_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs.base import (MeshConfig, OptimizerConfig, PrivacyConfig,
                                RunConfig, SHAPES)
from repro.configs.paper_models import MNIST_MLP3
from repro.core import barrier as barrier_mod, flatbuf
from repro.core.dp_pipeline import DPPipeline, reduce_contributions
from repro.core.noise_correction import init_state
from repro.data.synthetic import synthetic_mnist
from repro.distributed import steps as steps_mod
from repro.models.registry import Model
from repro.models.small import build_small_model

N = 4
sm = build_small_model(MNIST_MLP3)
model = Model(cfg=None, init=sm.init, loss=sm.loss, init_cache=None,
              prefill=None, decode_step=None)
priv = PrivacyConfig(enabled=True, sigma=0.5, clip_bound=1.0,
                     clip_mode="per_silo", sync_path="barrier")
mesh_cfg = MeshConfig((N,), ("data",))
train, _ = synthetic_mnist(n_train=128, n_test=16)
batch = {"x": jnp.asarray(train.x[:32]), "y": jnp.asarray(train.y[:32])}
params = model.init(jax.random.PRNGKey(0))
keys = barrier_mod.step_keys(jax.random.PRNGKey(9), jnp.zeros((), jnp.int32))
ns = init_state(jax.random.PRNGKey(0), n_silos=N)

mesh = jax.make_mesh((N,), ("data",), axis_types=(AxisType.Auto,))
for active_np in (np.ones(N, bool), np.array([True, False, True, True])):
    with jax.set_mesh(mesh):
        barrier, loss, norms, new_ns, bound = jax.jit(
            lambda p, b, a: steps_mod._barrier_grads(
                model, priv, mesh_cfg, p, b, keys, ns, jnp.float32(1.0),
                keys.key_clip, mesh, active=a))(params, batch,
                                                jnp.asarray(active_np))
    # fused tier on the same seed = the same engine, different placement
    fused, loss_f, _, _, _ = steps_mod._fused_grads(
        model, priv, params, batch, N, keys, ns, jnp.float32(1.0),
        keys.key_clip, active=jnp.asarray(active_np))
    err = max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
              for a, b in zip(jax.tree.leaves(barrier), jax.tree.leaves(fused)))
    print("active", active_np.tolist(), "barrier-vs-fused max err:", err)
    assert err < 1e-3, err
    assert abs(float(loss) - float(loss_f)) < 1e-5
# the packed psum, the tier's one collective, is scoped for a profile
with jax.set_mesh(mesh):
    text = jax.jit(lambda p, b: steps_mod._barrier_grads(
        model, priv, mesh_cfg, p, b, keys, ns, jnp.float32(1.0),
        keys.key_clip, mesh)).lower(params, batch).compile().as_text()
assert any("all-reduce" in l and "/dp.psum/" in l for l in text.splitlines())
print("OK")
"""


@pytest.mark.slow
def test_barrier_tier_parity_on_mesh():
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "HOME": "/root", "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", BARRIER_PARITY_SCRIPT],
                       capture_output=True, text=True, timeout=560, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# elastic trainer + accountant wiring


def test_session_train_elastic_with_schedule():
    from repro.api import Session

    sess = Session.from_config("qwen2.5-3b",
                               privacy=PrivacyConfig(enabled=True, sigma=0.5,
                                                     clip_bound=1.0,
                                                     n_silos=4))

    def schedule(step):
        return [True, True, step < 2, True]  # silo 2 drops from step 2

    res = sess.train(steps=4, batch_size=8, seq_len=32, log_every=0,
                     silo_schedule=schedule)
    assert res.step == 4
    contribs = [m["n_contributions"] for m in res.metrics]
    assert contribs == [4.0, 4.0, 3.0, 3.0]
    # the accountant recorded the per-step participation
    assert res.trainer.accountant.contributions == [4, 4, 3, 3]
    assert res.trainer.accountant.epsilon() > 0.0


def test_membership_drop_rejoin_quorum():
    from repro.runtime.elastic import SiloMembership

    m = SiloMembership(4, min_active=2)
    assert m.drop(3, step=0, cooldown=2)
    np.testing.assert_array_equal(m.active_at(0), [1, 1, 1, 0])
    np.testing.assert_array_equal(m.active_at(2), [1, 1, 1, 1])  # auto-rejoin
    assert m.drop(0, step=3)
    assert m.drop(1, step=3)
    assert not m.drop(2, step=3)  # would break the quorum
    assert m.n_active(3) == 2
    m.rejoin(0, step=4)
    assert m.n_active(4) == 3


def test_straggler_escalation_shrinks_active_set():
    """A straggling step escalates -> the trainer drops one silo for the
    cooldown window; training continues with the smaller participation set."""
    from repro.data.pipeline import FederatedBatcher
    from repro.runtime.trainer import Trainer, TrainerConfig

    sm = build_small_model(MNIST_MLP3)
    model = as_model(sm)
    rc = RunConfig(model=None, shape=SHAPES["train_4k"],
                   mesh=MeshConfig((1,), ("data",)),
                   privacy=PrivacyConfig(enabled=True, sigma=0.05,
                                         clip_bound=1.0, n_silos=4),
                   optimizer=OptimizerConfig(name="sgd", lr=0.1))
    train, _ = synthetic_mnist(n_train=256, n_test=16)
    batcher = FederatedBatcher(train.split(4), per_silo_batch=8)
    tcfg = TrainerConfig(total_steps=4, log_every=0, step_deadline_s=30.0,
                         elastic=True, elastic_cooldown=2)
    tr = Trainer(model, rc, tcfg,
                 lambda: {k: jnp.asarray(v) for k, v in batcher.next().items()})
    # simulate the policy reaching its escalation threshold
    for _ in range(tr.straggler.escalate_after):
        tr.straggler.observe(1e9)
    assert tr.membership.n_active(0) == 3  # one silo dropped
    state = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))
    state, step = tr.fit(state, jax.random.PRNGKey(1))
    assert step == 4
    tr._flush_metrics()
    contribs = [m["n_contributions"] for m in tr.metrics_log]
    assert contribs[0] == 3.0
    assert contribs[-1] == 4.0  # cooldown expired -> silo rejoined


def test_collaborative_session_dropout_and_rejoin():
    """Wire tier end to end: drop a dataset owner mid-run, rejoin it, keep
    training; the accountant records the contribution counts."""
    from repro.api import CollaborativeSession

    train, _ = synthetic_mnist(n_train=256, n_test=32)
    sess = CollaborativeSession.from_silos(
        [{"x": jnp.asarray(s.x), "y": jnp.asarray(s.y)}
         for s in train.split(4)],
        PrivacyConfig(enabled=True, sigma=0.05, clip_bound=1.0),
        session_id="elastic-demo", root_seed=0)
    sm = build_small_model(MNIST_MLP3)

    def grad_fn(params, data):
        return jax.value_and_grad(sm.loss)(params, data)

    def update_fn(params, update, lr):
        return jax.tree.map(lambda p, u: p - lr * u.astype(p.dtype),
                            params, update)

    params = sm.init(jax.random.PRNGKey(1))
    losses = []
    for step in range(6):
        if step == 2:
            assert sess.drop_silo(1, step=step)
        if step == 4:
            sess.rejoin_silo(1, step=step)
        params, loss = sess.step(step, params, grad_fn, update_fn, lr=0.5)
        losses.append(loss)
    assert losses[-1] < losses[0]
    assert sess.accountant.contributions == [4, 4, 3, 3, 4, 4]
    assert sess.epsilon() > 0.0


def test_admin_mask_mode_parity_wire_vs_fused():
    """mask_mode='admin' (the paper-faithful O(n*P) construction) through
    the same DPPipeline stages: wire-tier contribution sum == fused central
    aggregate == sum of clipped grads + xi_t - lam*xi_{t-1}, for full and
    partial participation sets."""
    import dataclasses

    from repro.core import clipping, masking

    model, priv, params, batch, keys = setup(lam=0.7)
    priv = dataclasses.replace(priv, mask_mode="admin")
    layout = flatbuf.layout_of(params)
    pipe = DPPipeline(priv, layout, N)
    ns = NoiseState(prev_key=jnp.array([7, 8], jnp.uint32),
                    has_prev=jnp.ones((), jnp.bool_),
                    prev_active=jnp.ones((N,), jnp.bool_))
    sigma_c = priv.sigma * 1.0

    for active_np in (np.ones(N, bool), np.array([True, False, True, True])):
        active = jnp.asarray(active_np)
        contribs = []
        for i in range(N):
            if not active_np[i]:
                continue
            sl = {k: v[i * 8:(i + 1) * 8] for k, v in batch.items()}
            g = jax.grad(model.loss)(params, sl)
            scale = pipe.clip_scale(pipe.norm_tree(g), 1.0)
            contribs.append(pipe.finalize(pipe.silo_contribution(
                g, i, scale, active, keys, ns, 1.0)))
        wire = reduce_contributions(contribs)

        fused, _, _, _, _ = steps_mod._fused_grads(
            model, priv, params, batch, N, keys, ns, jnp.float32(1.0),
            keys.key_clip, active=active)

        manual = None
        for i in range(N):
            if not active_np[i]:
                continue
            sl = {k: v[i * 8:(i + 1) * 8] for k, v in batch.items()}
            g = jax.grad(model.loss)(params, sl)
            g, _ = clipping.clip_tree(g, 1.0)
            manual = g if manual is None else jax.tree.map(
                lambda a, b: a + b, manual, g)
        xi = masking.admin_xi(jax.random.wrap_key_data(keys.key_xi), params,
                              sigma_c)
        xp = masking.admin_xi(jax.random.wrap_key_data(ns.prev_key), params,
                              sigma_c)
        manual = jax.tree.map(lambda m, a, b: m + a - 0.7 * b, manual, xi, xp)

        assert max_err(wire, fused) < 2e-4, active_np
        assert max_err(fused, manual) < 2e-4, active_np


def test_admin_mask_row_matches_stacked_set():
    """A handler reconstructing only its own row must get exactly the row of
    the admin's distributed set — same streams in every case, including the
    default all-active/no-correction one."""
    from repro.core import masking

    t = {"w": jnp.zeros((4096,), jnp.float32), "b": jnp.zeros((64,))}
    key = jax.random.PRNGKey(7)
    cases = [dict(active=None, correction=None),
             dict(active=np.array([True, False, True]), correction=None),
             dict(active=np.array([True, True, True]),
                  correction=jax.tree.map(lambda x: x + 0.25, t))]
    for kw in cases:
        masks = masking.admin_masks(key, t, 3, 1.5, 8.0, **kw)
        for i in range(3):
            row = masking.admin_mask_row(key, t, 3, i, 1.5, 8.0, **kw)
            for k in t:
                np.testing.assert_array_equal(np.asarray(row[k]),
                                              np.asarray(masks[k][i]), err_msg=f"{kw} silo {i} leaf {k}")


def test_admin_masks_telescope_over_partial_active_set():
    """Each silo's admin mask is wide-spread noise (property 2), rows of
    dropped silos are zero, and the active rows sum to exactly the xi (+
    correction) the central tier regenerates."""
    from repro.core import masking

    t = {"w": jnp.zeros((8192,), jnp.float32)}
    key = jax.random.PRNGKey(3)
    active = jnp.array([True, False, True, True])
    masks = masking.admin_masks(key, t, 4, 2.0, 16.0, active=active)
    m = np.asarray(masks["w"])
    assert np.all(m[1] == 0.0)  # dropped silo ships no mask
    assert m[0].std() > 10.0  # wide-spread vs sigma_c=2
    total = m[0] + m[2] + m[3]
    xi = np.asarray(masking.admin_xi(key, t, 2.0)["w"])
    np.testing.assert_allclose(total, xi, atol=1e-3)


def test_barrier_tier_pins_silo_count_to_mesh():
    """priv.n_silos must not leak into the barrier tier: the shard_map psum
    runs over the mesh's silo slots, so participation set, noise streams and
    divisor all use the mesh count."""
    priv = PrivacyConfig(enabled=True, sigma=0.5, n_silos=4,
                         sync_path="barrier")
    rc = RunConfig(model=None, shape=SHAPES["train_4k"],
                   mesh=MeshConfig((1,), ("data",)), privacy=priv)
    assert steps_mod.effective_n_silos(rc) == 1
    assert steps_mod.effective_n_silos(
        rc.replace(privacy=PrivacyConfig(enabled=True, sigma=0.5,
                                         n_silos=4))) == 4  # fused: priv wins


def test_legacy_checkpoint_without_prev_active_restores(tmp_path):
    """Checkpoints written before elastic membership (2-field NoiseState)
    must keep restoring: the missing participation leaf means 'all silos
    contributed'."""
    from repro.checkpoint import checkpointer
    from repro.data.pipeline import FederatedBatcher
    from repro.runtime.trainer import Trainer, TrainerConfig

    sm = build_small_model(MNIST_MLP3)
    model = as_model(sm)
    rc = RunConfig(model=None, shape=SHAPES["train_4k"],
                   mesh=MeshConfig((1,), ("data",)),
                   privacy=PrivacyConfig(enabled=True, sigma=0.05,
                                         clip_bound=1.0, n_silos=4),
                   optimizer=OptimizerConfig(name="sgd", lr=0.1))
    state = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))
    legacy = state._replace(noise_state=state.noise_state._replace(
        prev_active=None))
    checkpointer.save(tmp_path, 2, legacy, extra={})

    train, _ = synthetic_mnist(n_train=128, n_test=16)
    batcher = FederatedBatcher(train.split(4), per_silo_batch=8)
    tr = Trainer(model, rc, TrainerConfig(total_steps=4, log_every=0,
                                          checkpoint_dir=str(tmp_path)),
                 lambda: {k: jnp.asarray(v) for k, v in batcher.next().items()})
    restored, step = tr.fit(state, jax.random.PRNGKey(1))
    assert step == 4
    np.testing.assert_array_equal(
        np.asarray(restored.noise_state.prev_active), np.ones(4, bool))


def test_wire_dropout_matches_k_silo_ground_truth():
    """A dropped owner's absence is invisible in the aggregate: the updater's
    sum over k active handlers equals the k-silo manual construction."""
    model, priv, params, batch, keys = setup()
    layout = flatbuf.layout_of(params)
    pipe = DPPipeline(priv, layout, N)
    ns = init_state(jax.random.PRNGKey(0), n_silos=N)
    active_np = np.array([True, False, True, True])
    active = jnp.asarray(active_np)
    contribs = []
    for i in range(N):
        if not active_np[i]:
            continue
        sl = {k: v[i * 8:(i + 1) * 8] for k, v in batch.items()}
        g = jax.grad(model.loss)(params, sl)
        scale = pipe.clip_scale(pipe.norm_tree(g), 1.0)
        contribs.append(pipe.finalize(pipe.silo_contribution(
            g, i, scale, active, keys, ns, 1.0)))
    wire = reduce_contributions(contribs)
    manual = manual_aggregate(model, params, batch, keys, active_np)
    assert max_err(wire, manual) < 2e-4
