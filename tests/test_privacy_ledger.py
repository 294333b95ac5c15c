"""Per-silo privacy ledger (core/privacy/): parity with the legacy scalar
accountant, per-silo epsilon under dropout, budget enforcement on the
in-process and wire tiers, and persistence (ledger round-trip + legacy
PrivacyAccountant restore)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (MeshConfig, OptimizerConfig, PrivacyConfig,
                                RunConfig, SHAPES)
from repro.configs.paper_models import MNIST_MLP3
from repro.core.privacy import PrivacyAccountant, PrivacyLedger
from repro.data.pipeline import FederatedBatcher
from repro.data.synthetic import synthetic_mnist
from repro.distributed import steps as steps_mod
from repro.models.registry import Model
from repro.models.small import build_small_model
from repro.runtime.trainer import Trainer, TrainerConfig


def as_model(sm):
    return Model(cfg=None, init=sm.init, loss=sm.loss, init_cache=None,
                 prefill=None, decode_step=None)


def mlp_run_config():
    # sigma large enough that the analytic epsilon is finite after one step
    # (per-silo epsilon comparisons need finite values)
    return RunConfig(
        model=None, shape=SHAPES["train_4k"], mesh=MeshConfig((1,), ("data",)),
        privacy=PrivacyConfig(enabled=True, sigma=0.5, clip_bound=1.0,
                              n_silos=4),
        optimizer=OptimizerConfig(name="sgd", lr=0.1))


def mlp_trainer(tmp_path=None, total_steps=4, uniform=None, budgets=None,
                **tcfg_kw):
    sm = build_small_model(MNIST_MLP3)
    model = as_model(sm)
    rc = mlp_run_config()
    train, _ = synthetic_mnist(n_train=256, n_test=16)
    batcher = FederatedBatcher(train.split(4), per_silo_batch=8)
    tcfg = TrainerConfig(total_steps=total_steps, log_every=0,
                         checkpoint_dir=str(tmp_path) if tmp_path else None,
                         checkpoint_every=2, silo_epsilon_budget=uniform,
                         silo_budgets=budgets, **tcfg_kw)
    tr = Trainer(model, rc, tcfg,
                 lambda: {k: jnp.asarray(v) for k, v in batcher.next().items()})
    return tr, model, rc


# ---------------------------------------------------------------------------
# parity with the legacy scalar accountant (acceptance: bit-for-bit)


def test_all_active_matches_legacy_accountant_analytic():
    acc = PrivacyAccountant(sigma=0.7, delta=1e-5, lam=0.3)
    led = PrivacyLedger(sigma=0.7, delta=1e-5, n_silos=4, lam=0.3)
    for _ in range(25):
        acc.step(contributions=4)
        led.record(np.ones(4, bool))
    assert led.epsilon() == acc.epsilon()  # exact, same closed form
    for i in range(4):
        assert led.epsilon(i) == acc.epsilon()
    assert led.contributions == acc.contributions
    assert led.steps == acc.steps


def test_all_active_matches_legacy_accountant_rdp():
    acc = PrivacyAccountant(sigma=2.0, delta=1e-5, q=0.1, mode="rdp")
    led = PrivacyLedger(sigma=2.0, delta=1e-5, n_silos=3, q=0.1, mode="rdp")
    for _ in range(20):
        acc.step()
        led.record()
    # identical repeated addition of the identical per-step increment
    assert led.epsilon() == acc.epsilon()
    for i in range(3):
        assert led.epsilon(i) == acc.epsilon()


def test_dropout_differentiates_per_silo_epsilon():
    led = PrivacyLedger(sigma=0.5, delta=1e-5, n_silos=3)
    schedule = [[1, 1, 1], [1, 0, 1], [1, 0, 0], [1, 1, 1]]
    for mask in schedule:
        led.record(np.asarray(mask, bool))
    assert led.silo_steps(0) == 4 and led.silo_steps(1) == 2 \
        and led.silo_steps(2) == 3
    assert led.epsilon(1) < led.epsilon(2) < led.epsilon(0)
    assert led.epsilon(0) == led.epsilon()  # full participation == global
    np.testing.assert_array_equal(led.participation(), np.asarray(schedule,
                                                                  bool))


def test_sitting_out_monotone_property():
    """A silo sitting out k steps always has eps <= the all-steps silo
    (monotonicity under dropout), in both accounting modes."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(deadline=None, max_examples=25)
    @given(st.integers(1, 60), st.lists(st.booleans(), min_size=1,
                                        max_size=60),
           st.sampled_from(["analytic", "rdp"]))
    def run(steps, sit_out_pattern, mode):
        led = PrivacyLedger(sigma=1.5, delta=1e-5, n_silos=2, q=0.5,
                            mode=mode)
        for t in range(steps):
            out = sit_out_pattern[t % len(sit_out_pattern)]
            led.record(np.array([True, not out]))
        assert led.epsilon(1) <= led.epsilon(0) + 1e-12
        assert led.epsilon(0) <= led.epsilon() + 1e-12

    run()


# ---------------------------------------------------------------------------
# budgets & enforcement primitives


def test_budget_exhaustion_and_verdicts():
    led = PrivacyLedger(sigma=0.5, delta=1e-5, n_silos=3,
                        epsilon_budget=50.0, budgets={2: 15.0})
    assert list(led.allowed_mask()) == [True, True, True]
    while not led.silo_exhausted(2):
        led.record([True, False, True])
    assert led.budget_for(2) == 15.0 and led.budget_for(0) == 50.0
    assert list(led.allowed_mask()) == [True, True, False]
    assert led.take_exclusions() == [2]
    assert led.take_exclusions() == []  # drained once
    report = led.spend_report()
    assert report["silos"][2]["exhausted"]
    assert report["silos"][1]["epsilon"] == 0.0  # never contributed
    json.dumps(report)  # admin-plane artifact must be serializable


def test_membership_honors_budget_exclusion():
    from repro.runtime.elastic import SiloMembership

    m = SiloMembership(4, cooldown_steps=2)
    m.exclude(1, step=5, reason="budget")
    np.testing.assert_array_equal(m.active_at(5), [1, 0, 1, 1])
    # cooldown expiry never revives a budget exclusion
    np.testing.assert_array_equal(m.active_at(50), [1, 0, 1, 1])
    assert not m.rejoin(1, step=50)  # refused without override
    np.testing.assert_array_equal(m.active_at(50), [1, 0, 1, 1])
    assert m.rejoin(1, step=51, override=True)  # operator decision
    np.testing.assert_array_equal(m.active_at(51), [1, 1, 1, 1])


# ---------------------------------------------------------------------------
# in-process tier: trainer consults the ledger each step


def test_trainer_excludes_exhausted_silo_next_step():
    """Silo 1 gets a tiny budget: it contributes to step 0, is exhausted by
    the recording, and is excluded from step 1's participation set on."""
    tr, model, rc = mlp_trainer(total_steps=3, budgets={1: 0.001})
    state = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))
    state, step = tr.fit(state, jax.random.PRNGKey(1))
    assert step == 3
    tr._flush_metrics()
    contribs = [m["n_contributions"] for m in tr.metrics_log]
    assert contribs == [4.0, 3.0, 3.0]
    assert tr.membership is not None and tr.membership.excluded == (1,)
    assert tr.accountant.silo_steps(1) == 1
    assert tr.accountant.epsilon(1) < tr.accountant.epsilon(0)
    per_silo = tr.metrics_log[-1]["epsilon_per_silo"]
    assert per_silo[1] < per_silo[0]


BARRIER_RESTORE_SCRIPT = r"""
import sys
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import (MeshConfig, OptimizerConfig, PrivacyConfig,
                                RunConfig, SHAPES)
from repro.configs.paper_models import MNIST_MLP3
from repro.core import barrier as barrier_mod, clipping, flatbuf
from repro.data.synthetic import synthetic_mnist
from repro.distributed import steps as steps_mod
from repro.kernels.dp_fused.ref import _stream
from repro.models.registry import Model
from repro.models.small import build_small_model
from repro.runtime.trainer import Trainer, TrainerConfig

N, SIGMA, LAM = 4, 0.5, 0.7
ckpt = sys.argv[1]
sm = build_small_model(MNIST_MLP3)
model = Model(cfg=None, init=sm.init, loss=sm.loss, init_cache=None,
              prefill=None, decode_step=None)
rc = RunConfig(model=None, shape=SHAPES["train_4k"],
               mesh=MeshConfig((N,), ("data",)),
               privacy=PrivacyConfig(enabled=True, sigma=SIGMA, clip_bound=1.0,
                                     noise_lambda=LAM, sync_path="barrier"),
               optimizer=OptimizerConfig(name="sgd", lr=1.0))
train, _ = synthetic_mnist(n_train=128, n_test=16)
batch = {"x": jnp.asarray(train.x[:32]), "y": jnp.asarray(train.y[:32])}
root = jax.random.PRNGKey(1)


def trainer(total_steps, budgets=None):
    return Trainer(model, rc, TrainerConfig(
        total_steps=total_steps, log_every=0, checkpoint_dir=ckpt,
        checkpoint_every=1, silo_budgets=budgets), lambda: batch)


# step 0 with every silo; silo 1's budget is spent by it
tr1 = trainer(1, budgets={1: 0.001})
state1, _ = tr1.fit(steps_mod.init_train_state(model, rc,
                                               jax.random.PRNGKey(0)), root)
state1 = jax.device_get(state1)
assert tr1.accountant.silo_exhausted(1)

# resumed without budget flags: the checkpoint's budgets exclude silo 1
tr2 = trainer(2)
state2, step = tr2.fit(steps_mod.init_train_state(model, rc,
                                                  jax.random.PRNGKey(0)), root)
assert step == 2
tr2._flush_metrics()
assert [m["n_contributions"] for m in tr2.metrics_log] == [4.0, 3.0]
assert tr2.membership.excluded == (1,)

# the step-1 aggregate, read back through SGD at lr 1 over 3 contributors
layout = flatbuf.layout_of(state1.params)
agg = (flatbuf.pack(layout, state1.params)
       - flatbuf.pack(layout, jax.device_get(state2.params))) * 3.0
active = [True, False, True, True]
keys = barrier_mod.step_keys(root, jnp.asarray(1, jnp.int32))
ns = state1.noise_state
assert bool(ns.has_prev) and bool(np.all(ns.prev_active))
idx = jnp.arange(layout.total, dtype=jnp.uint32)
manual = jnp.zeros((layout.total,), jnp.float32)
for i in range(N):
    if not active[i]:
        continue
    sl = {k: v[i * 8:(i + 1) * 8] for k, v in batch.items()}
    g, _ = clipping.clip_tree(jax.grad(model.loss)(state1.params, sl), 1.0)
    manual = manual + flatbuf.pack(layout, g)
    manual = manual + SIGMA / np.sqrt(3.0) * _stream(keys.key_xi, idx,
                                                     jnp.uint32(i))
    manual = manual - LAM * SIGMA / np.sqrt(4.0) * _stream(
        ns.prev_key, idx, jnp.uint32(i))
# compared leaf by leaf: the streams also cover the layout's padding
err = max(float(jnp.abs(a - b).max()) for a, b in zip(
    jax.tree.leaves(flatbuf.unpack(layout, agg)),
    jax.tree.leaves(flatbuf.unpack(layout, manual))))
print("restored barrier step vs active-ring aggregate max err:", err)
assert err < 1e-3, err
print("OK")
"""


@pytest.mark.slow
def test_barrier_restore_with_budgets_steps_partial_set(tmp_path):
    """A barrier-tier Trainer resumed from a checkpoint whose budgets exclude
    a silo steps over the partial participation set: its aggregate is the
    active ring's clipped grads plus their noise and correction shares
    (4 host devices, so in a subprocess)."""
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(root / "src"), "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path), "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, "-c", BARRIER_RESTORE_SCRIPT, str(tmp_path / "ck")],
        capture_output=True, text=True, timeout=560, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout


def test_trainer_stops_when_all_budgets_spent():
    tr, model, rc = mlp_trainer(total_steps=100, uniform=0.001)
    state = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))
    state, step = tr.fit(state, jax.random.PRNGKey(1))
    assert step == 1  # one step spends every silo's budget; DP stops the run
    assert all(tr.accountant.silo_exhausted(i) for i in range(4))


def test_budget_raise_reenables_and_second_exhaustion_fires():
    """An operator budget raise re-admits the silo; a later re-exhaustion
    must fire a fresh event + exclusion decision (not be swallowed by the
    seen-set)."""
    led = PrivacyLedger(sigma=0.5, delta=1e-5, n_silos=2, budgets={0: 10.0})
    while not led.silo_exhausted(0):
        led.record([True, True])
    assert led.take_exclusions() == [0]
    led.budgets[0] = 100.0  # operator grants more budget
    assert led.take_exclusions() == []
    assert led.allowed_mask()[0]
    while not led.silo_exhausted(0):
        led.record([True, True])
    assert led.take_exclusions() == [0]  # second exhaustion fires again
    assert sum(1 for e in led.events
               if e["action"] == "budget_exhausted") == 2


def test_spend_report_is_strict_json_with_infinite_epsilon():
    led = PrivacyLedger(sigma=1e-4, delta=1e-5, n_silos=1, epsilon_budget=5.0)
    led.record([True])
    assert led.epsilon(0) == float("inf")
    report = led.spend_report()
    json.dumps(report, allow_nan=False)  # no bare Infinity tokens
    assert report["silos"][0]["epsilon"] is None
    assert report["exclusions"][0]["epsilon"] is None


# ---------------------------------------------------------------------------
# persistence: ledger round-trip + legacy accountant restore


def test_ledger_checkpoint_roundtrip(tmp_path):
    tr, model, rc = mlp_trainer(tmp_path, total_steps=4, budgets={2: 0.001})
    state = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))
    state, step = tr.fit(state, jax.random.PRNGKey(1))
    assert step == 4

    tr2, _, _ = mlp_trainer(tmp_path, total_steps=6, budgets={2: 0.001})
    state2 = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))
    state2, step2 = tr2.fit(state2, jax.random.PRNGKey(1))
    assert step2 == 6
    led, led2 = tr.accountant, tr2.accountant
    assert led2.steps == 6
    assert led2.history[:4] == led.history
    assert led2.silo_steps(2) == 1  # exclusion survived the restart
    assert led2.epsilon(2) == led.epsilon(2)
    assert tr2.membership.excluded == (2,)


def test_checkpoint_budgets_enforce_without_configured_flags(tmp_path):
    """A resume that doesn't re-pass budget flags must keep enforcing the
    checkpointed budgets, including recording exclusion decisions (the
    restore creates the membership layer the decisions land in)."""
    tr, model, rc = mlp_trainer(tmp_path, total_steps=2, budgets={1: 20.0})
    state = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))
    state, step = tr.fit(state, jax.random.PRNGKey(1))
    assert step == 2 and not tr.accountant.silo_exhausted(1)

    tr2, _, _ = mlp_trainer(tmp_path, total_steps=5)  # no budget flags
    state2 = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))
    state2, step2 = tr2.fit(state2, jax.random.PRNGKey(1))
    assert step2 == 5
    assert tr2.accountant.budget_for(1) == 20.0  # survived the restart
    assert tr2.accountant.silo_exhausted(1)
    assert tr2.membership is not None and tr2.membership.excluded == (1,)
    contribs = [m["n_contributions"] for m in tr2.metrics_log]
    assert contribs[-1] == 3.0  # silo 1 out after its budget was spent


def test_legacy_accountant_state_restores_into_ledger(tmp_path):
    """A pre-refactor checkpoint (scalar PrivacyAccountant state dict in the
    `accountant` extra) restores into a working all-silos-identical ledger."""
    from repro.checkpoint import checkpointer

    legacy = PrivacyAccountant(sigma=0.5, delta=1e-5)
    legacy.step(2, contributions=4)

    tr, model, rc = mlp_trainer(tmp_path, total_steps=4)
    state = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))
    checkpointer.save(tmp_path, 2, state,
                      extra={"accountant": legacy.state_dict()})
    state, step = tr.fit(state, jax.random.PRNGKey(1))
    assert step == 4
    led = tr.accountant
    assert led.steps == 4
    assert led.contributions[:2] == [4, 4]  # legacy steps = all-active
    for i in range(4):
        assert led.silo_steps(i) == 4
        assert led.epsilon(i) == led.epsilon()


def test_legacy_state_dict_direct_mapping():
    legacy = PrivacyAccountant(sigma=2.0, delta=1e-5, lam=0.5, q=0.1,
                               mode="rdp")
    legacy.step(30)
    led = PrivacyLedger.from_state_dict(legacy.state_dict(), n_silos=5)
    assert led.n_silos == 5 and led.steps == 30
    assert led.epsilon() == legacy.epsilon()
    for i in range(5):
        assert led.epsilon(i) == legacy.epsilon()
    # and the mapped ledger keeps composing correctly
    led.record(np.array([True] + [False] * 4))
    assert led.silo_steps(0) == 31 and led.silo_steps(1) == 30
    assert led.epsilon(0) > led.epsilon(1)


# ---------------------------------------------------------------------------
# wire tier: admin verdicts + in-TEE refusal


def test_wire_tier_budget_enforcement():
    from repro.api import CollaborativeSession

    train, _ = synthetic_mnist(n_train=256, n_test=32)
    sess = CollaborativeSession.from_silos(
        [{"x": jnp.asarray(s.x), "y": jnp.asarray(s.y)}
         for s in train.split(4)],
        PrivacyConfig(enabled=True, sigma=0.5, clip_bound=1.0),
        session_id="budget-demo", root_seed=0,
        silo_budgets={1: 0.001})
    sm = build_small_model(MNIST_MLP3)

    def grad_fn(params, data):
        return jax.value_and_grad(sm.loss)(params, data)

    def update_fn(params, update, lr):
        return jax.tree.map(lambda p, u: p - lr * u.astype(p.dtype),
                            params, update)

    params = sm.init(jax.random.PRNGKey(1))
    for step in range(3):
        params, _ = sess.step(step, params, grad_fn, update_fn, lr=0.5)
    # silo 1 contributed to round 0 only; verdicts excluded it after
    assert sess.accountant.contributions == [4, 3, 3]
    assert sess.accountant.silo_steps(1) == 1
    assert sess.epsilon(1) < sess.epsilon(0)
    assert sess.membership.excluded == (1,)
    # enforcement sits inside the TEE boundary: the handler fetches the
    # verdicts from its attested admin, so a malicious driver can neither
    # omit them (no verdicts kwarg) ...
    verdicts = sess.admin.verdicts()
    assert not verdicts[1]
    from repro.core.tee.components import _ser
    with pytest.raises(PermissionError):
        sess.handlers[1].compute_update(
            _ser(params), grad_fn, sess.privacy,
            sess.admin.keys_for_step(3), sess.n_silos, clip_bound=1.0)
    # ... nor fabricate an all-allowed vector
    with pytest.raises(PermissionError):
        sess.handlers[1].compute_update(
            _ser(params), grad_fn, sess.privacy,
            sess.admin.keys_for_step(3), sess.n_silos, clip_bound=1.0,
            verdicts=np.ones(4, bool))
    # no rejoin without operator override; and even then the verdict holds
    assert not sess.rejoin_silo(1)
    assert sess.rejoin_silo(1, override=True)
    assert not sess.admin.verdicts()[1]
    report = sess.privacy_report()
    assert report["silos"][1]["exhausted"] and not report["silos"][0]["exhausted"]


def test_wire_ledger_uses_thm1_effective_scale():
    """Both tiers must compute the same epsilon for one PrivacyConfig: the
    per-step noise is sigma/(1-lam) and the ledger's internal (1-lam) brings
    the effective per-release scale back to sigma (Thm. 1), matching the
    Trainer's convention and the old wire accountant's epsilon."""
    from repro.api import CollaborativeSession

    train, _ = synthetic_mnist(n_train=64, n_test=8)
    sess = CollaborativeSession.from_silos(
        [{"x": jnp.asarray(s.x), "y": jnp.asarray(s.y)}
         for s in train.split(2)],
        PrivacyConfig(enabled=True, sigma=0.5, noise_lambda=0.7,
                      clip_bound=1.0))
    led = sess.accountant
    assert abs(led.sigma * (1.0 - led.lam) - 0.5) < 1e-9
    led.record(None)
    legacy = PrivacyAccountant(sigma=0.5, delta=led.delta)
    legacy.step()
    assert led.epsilon() == legacy.epsilon()


def test_ledger_config_joins_attestation_measurement():
    """Two sessions differing only in budgets must measure differently (a
    component launched against different enforcement terms gets no keys)."""
    from repro.core.tee.components import ManagementService

    priv = PrivacyConfig(enabled=True, sigma=0.5)
    a, b = ManagementService(), ManagementService()
    a.create_session("s", 4, priv, ledger_config={"epsilon_budget": 1.0})
    b.create_session("s", 4, priv, ledger_config={"epsilon_budget": 2.0})
    assert a.expected_measurement() != b.expected_measurement()
    c = ManagementService()
    c.create_session("s", 4, priv, ledger_config={"epsilon_budget": 1.0})
    assert a.expected_measurement() == c.expected_measurement()
    # one service binds one enforcement config for all its keys
    with pytest.raises(ValueError):
        a.create_session("s2", 4, priv, ledger_config={"epsilon_budget": 9.0})


def test_component_launched_with_wrong_config_gets_no_keys():
    """The component measures its *own* launch-time ledger config; one
    deployed against different enforcement terms fails the KDS gate."""
    from repro.core.tee.channels import derive_key
    from repro.core.tee.components import DataHandler, ManagementService

    priv = PrivacyConfig(enabled=True, sigma=0.5)
    svc = ManagementService()
    svc.create_session("s", 2, priv, ledger_config={"epsilon_budget": 1.0})
    good = DataHandler("h-good", svc, silo_idx=0)  # deployed under the config
    bad = DataHandler("h-bad", svc, silo_idx=1)
    bad.launch_ledger_config = {"epsilon_budget": 99.0}  # laxer terms
    good.attest(svc.policy)
    bad.attest(svc.policy)
    svc.kds.upload_key("dk", derive_key(b"r", "dk"), "owner",
                       svc.expected_measurement(), svc.policy.hash())
    assert svc.kds.request_key("dk", good.report)
    with pytest.raises(PermissionError):
        svc.kds.request_key("dk", bad.report)
