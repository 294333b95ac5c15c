"""Host-side training loop with fault tolerance: checkpoint/restart, DP-budget
persistence, straggler deadlines, preemption handling.

The inner step is the jitted CITADEL++ train step (distributed/steps.py); this
loop owns everything jit can't: the accountant (its state must survive
restarts — the privacy guarantee composes over *all* steps ever taken), the
data-iterator state, checkpoint cadence, and wall-clock policies.

Each step's host work is traced with ``jax.profiler.TraceAnnotation`` spans,
inert unless a profiler is running: ``train.next_batch``, ``train.dispatch``
(the jitted step call), ``train.ledger`` (the privacy ledger's record and
epsilon reads), ``train.flush`` (metrics to the host) and
``train.checkpoint``.
"""
from __future__ import annotations

import signal
import time
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint import checkpointer
from repro.configs.base import RunConfig
from repro.core.privacy import PrivacyLedger
from repro.distributed import steps as steps_mod
from repro.runtime.elastic import SiloMembership
from repro.runtime.straggler import SiloTelemetry, StragglerPolicy


@dataclass
class TrainerConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    checkpoint_dir: Optional[str] = None
    keep_checkpoints: int = 3
    log_every: int = 10
    # privacy budget stop: halt when the global epsilon(delta) exceeds this
    # (the paper's "no further training is allowed by DP" semantics, Fig. 6)
    epsilon_budget: Optional[float] = None
    # per-silo budgets (the ledger's enforcement layer): a uniform per-silo
    # epsilon, optionally overridden per silo via ``silo_budgets``. A silo
    # whose own spend reaches its budget is excluded from the participation
    # set (no rejoin until operator override); training stops once no silo
    # may contribute
    silo_epsilon_budget: Optional[float] = None
    silo_budgets: Optional[dict] = None  # silo index -> epsilon override
    # straggler deadline. When set, every step blocks on the device result so
    # the deadline compares against true step time; when None (adaptive EMA),
    # steps stay fully async and the policy observes the amortized per-step
    # wall time at each metrics flush instead
    step_deadline_s: Optional[float] = None
    metrics_flush_every: int = 50  # bound on how long metrics stay on-device
    # elastic silo membership: thread a per-step participation set through the
    # jitted step (the DP engine keeps the mask/noise invariants over any
    # active subset) and let straggler escalations drop a silo for
    # ``elastic_cooldown`` steps instead of only logging a reschedule request
    elastic: bool = False
    elastic_cooldown: int = 10
    elastic_min_active: int = 1


@dataclass
class Trainer:
    model: object
    run_cfg: RunConfig
    tcfg: TrainerConfig
    next_batch: Callable[[], dict]
    batch_state: Optional[object] = None  # object with state_dict/load_state_dict
    mesh: Optional[object] = None
    # elastic membership: who contributes each step. ``silo_schedule``
    # (step -> bool sequence) overrides ``membership`` when given — handy for
    # deterministic dropout/rejoin scenarios and tests
    membership: Optional[SiloMembership] = None
    silo_schedule: Optional[Callable[[int], Sequence[bool]]] = None
    # straggler attribution: simulated per-silo latencies on the fused tiers
    # (step -> (n_silos,) seconds) feeding SiloTelemetry, so escalations drop
    # the actually-slow silo; on the barrier/wire tiers real per-host timing
    # feeds ``telemetry.observe`` instead
    silo_latency_hook: Optional[Callable[[int], Sequence[float]]] = None
    metrics_log: list = field(default_factory=list)
    _preempted: bool = False
    _pending: list = field(default_factory=list)  # on-device metric entries
    _window_t0: Optional[float] = None  # flush-window start (adaptive mode)
    _step: int = 0  # current step (straggler escalation needs it)

    def __post_init__(self):
        priv = self.run_cfg.privacy
        self.n_silos = steps_mod.effective_n_silos(self.run_cfg)
        self.accountant = PrivacyLedger.from_privacy_config(
            priv, self.n_silos,
            epsilon_budget=self.tcfg.silo_epsilon_budget,
            budgets=self.tcfg.silo_budgets) if priv.enabled else None
        self.straggler = StragglerPolicy(self.tcfg.step_deadline_s)
        self.telemetry = SiloTelemetry(self.n_silos)
        self._owns_mesh = False
        if priv.enabled and priv.sync_path == "barrier" and self.mesh is None:
            # the barrier tier shard_maps over the silo axes; the
            # Session/CLI path doesn't carry a mesh, so build one from the
            # run config and make it ambient for the whole fit
            from repro.launch.mesh import make_mesh_from_config
            self.mesh = make_mesh_from_config(self.run_cfg.mesh)
            self._owns_mesh = True
        if self.tcfg.elastic and self.membership is None:
            self.membership = SiloMembership(
                self.n_silos, min_active=self.tcfg.elastic_min_active,
                cooldown_steps=self.tcfg.elastic_cooldown)
        if self.membership is None and self.accountant is not None \
                and self.accountant.has_budgets():
            # per-silo budgets need a membership layer to honor exclusion
            # decisions even on non-elastic runs
            self.membership = SiloMembership(self.n_silos)
        if self.tcfg.elastic and self.straggler.on_escalate is None \
                and self.silo_schedule is None:
            # escalation drops one silo for the cooldown window; per-silo
            # step-time telemetry names the actually-slow silo (highest-index
            # fallback when nothing has been observed yet). Not wired when a
            # silo_schedule pins the participation set — the schedule is
            # authoritative and a shadow drop would only consume quorum
            # without ever taking effect
            self.straggler.on_escalate = lambda decision: \
                self.membership.drop_one(self._step,
                                         telemetry=self.telemetry)
        self.train_step = steps_mod.build_train_step(
            self.model, self.run_cfg, abstract_mesh=self.mesh)
        self._jit_step = jax.jit(self.train_step, donate_argnums=(0,))

    def _active_for(self, step: int):
        """This step's participation set, or ``None`` when no source of
        partial participation is armed (no schedule, no membership layer,
        no budgets) — i.e. the set is *statically* all-active. None is the
        signal to omit the jit argument so the DP engine traces its
        fixed-ring fast path; every consumer (ledger ``record``, metrics)
        treats None as all-silos-contributed. The one place deciding this —
        a new participation source added here is automatically honoured by
        the step call."""
        if self.silo_schedule is not None:
            active = np.asarray(self.silo_schedule(step), bool)
        elif self.membership is not None:
            active = self.membership.active_at(step)
        elif self.accountant is not None and self.accountant.has_budgets():
            active = np.ones(self.n_silos, bool)
        else:
            return None  # statically all-active
        if self.accountant is not None and self.accountant.has_budgets():
            # budget verdicts override every membership source — a silo with
            # no budget left may not contribute even if scheduled
            active = active & self.accountant.allowed_mask()
        return active

    def _enforce_budgets(self, step: int) -> None:
        """Turn the ledger's fresh exclusion decisions into membership drops
        (budget-driven: no cooldown, no rejoin until operator override)."""
        if self.accountant is None:
            return
        for silo in self.accountant.take_exclusions():
            if self.membership is not None:
                self.membership.exclude(silo, step=step, reason="budget")

    def spend_report(self) -> Optional[dict]:
        """The ledger's admin-plane spend report (None without privacy),
        with per-silo round-trip EMAs when telemetry has observations."""
        if not self.accountant:
            return None
        return self.accountant.spend_report(
            round_trip_s=self.telemetry.snapshot())

    # -- preemption --------------------------------------------------------
    def install_preemption_handler(self):
        def handler(signum, frame):
            self._preempted = True
        signal.signal(signal.SIGTERM, handler)

    # -- metrics -----------------------------------------------------------
    def _flush_metrics(self):
        """Convert pending on-device metric entries to host floats in one
        transfer. Keeping per-step metrics on-device avoids a device sync
        every step (the jitted step stays fully async between boundaries).
        This is also where step time is observed: the transfer drains the
        dispatch queue, so window wall time / window steps is the honest
        per-step time. Each flushed entry's ``step_time_s`` is that value;
        in adaptive straggler mode it also re-anchors the policy's EMA."""
        if not self._pending:
            return
        with TraceAnnotation("train.flush"):
            n = len(self._pending)
            host = jax.device_get(self._pending)
            self._pending.clear()
            step_time_s = None if self._window_t0 is None \
                else (time.time() - self._window_t0) / n
            for entry in host:
                self.metrics_log.append({
                    k: float(v) if isinstance(v, (np.ndarray, np.floating))
                    else v
                    for k, v in entry.items()} | {"step_time_s": step_time_s})
            if self.tcfg.step_deadline_s is None and step_time_s is not None:
                # authoritative amortized step time for this window
                # re-anchors the adaptive EMA; per-step enqueue times
                # (observed once calibrated) then catch individual stalls
                # via back-pressure
                self.straggler.calibrate(step_time_s)
            self._window_t0 = time.time()

    # -- checkpointing -----------------------------------------------------
    def _save(self, state, step: int):
        with TraceAnnotation("train.checkpoint"):
            self._flush_metrics()
            if not self.tcfg.checkpoint_dir:
                return
            extra = {
                "accountant": (self.accountant.state_dict()
                               if self.accountant else None),
                "batch_state": (self.batch_state.state_dict()
                                if self.batch_state is not None else None),
                # metrics history must survive preemption/restart, or the
                # run's loss/epsilon curves silently truncate at the restore
                # point
                "metrics_log": list(self.metrics_log),
            }
            checkpointer.save(self.tcfg.checkpoint_dir, step, state, extra)
            checkpointer.garbage_collect(self.tcfg.checkpoint_dir,
                                         self.tcfg.keep_checkpoints)

    def try_restore(self, state):
        """Resume from the latest complete checkpoint if one exists."""
        if not self.tcfg.checkpoint_dir:
            return state, 0
        last = checkpointer.latest_step(self.tcfg.checkpoint_dir)
        if last is None:
            return state, 0
        try:
            state, extra, step = checkpointer.restore(self.tcfg.checkpoint_dir,
                                                      state)
        except KeyError:
            # legacy checkpoint written before elastic membership: no
            # noise_state.prev_active leaf. Restore with the 2-field state
            # and treat the pre-restore history as all-active
            legacy = state._replace(
                noise_state=state.noise_state._replace(prev_active=None))
            restored, extra, step = checkpointer.restore(
                self.tcfg.checkpoint_dir, legacy)
            state = restored._replace(noise_state=restored.noise_state._replace(
                prev_active=jnp.ones((self.n_silos,), jnp.bool_)))
        if self.accountant and extra.get("accountant"):
            # restores both ledger state dicts and pre-refactor scalar
            # PrivacyAccountant dicts (legacy -> all-silos-identical ledger);
            # the operator's configured budgets stay authoritative
            restored_ledger = PrivacyLedger.from_state_dict(
                extra["accountant"], n_silos=self.n_silos)
            # operator-configured budgets win when given; otherwise the
            # checkpointed budgets keep enforcing across the restart
            if self.tcfg.silo_epsilon_budget is not None:
                restored_ledger.epsilon_budget = self.tcfg.silo_epsilon_budget
            if self.tcfg.silo_budgets:
                restored_ledger.budgets = dict(self.tcfg.silo_budgets)
            self.accountant = restored_ledger
            if self.membership is None and restored_ledger.has_budgets():
                # budgets carried only by the checkpoint still need a
                # membership layer to record exclusion decisions
                self.membership = SiloMembership(self.n_silos)
            if self.membership is not None:
                # re-apply standing exclusion decisions (the pending queue is
                # not persisted; what matters is who is exhausted *now*)
                for silo in self.accountant.exhausted():
                    self.membership.exclude(silo, step=step, reason="budget")
        if self.batch_state is not None and extra.get("batch_state"):
            self.batch_state.load_state_dict(extra["batch_state"])
        if extra.get("metrics_log"):
            self.metrics_log = list(extra["metrics_log"])
        return state, step

    # -- main loop ---------------------------------------------------------
    def fit(self, state, root_key) -> tuple:
        from contextlib import ExitStack

        with ExitStack() as stack:
            if self._owns_mesh:
                stack.enter_context(jax.set_mesh(self.mesh))
            return self._fit(state, root_key)

    def _fit(self, state, root_key) -> tuple:
        state, start = self.try_restore(state)
        step = start
        while step < self.tcfg.total_steps:
            if self._preempted:
                self._save(state, step)
                return state, step
            if (self.tcfg.epsilon_budget is not None and self.accountant
                    and self.accountant.epsilon() >= self.tcfg.epsilon_budget):
                break  # privacy budget exhausted: DP forbids further training

            active = self._active_for(step)  # None = statically all-active
            if active is not None and not active.any():
                # every silo is out (budgets spent or membership empty):
                # there is nothing DP allows to aggregate
                break

            with TraceAnnotation("train.next_batch"):
                batch = self.next_batch()
            if self._window_t0 is None:
                self._window_t0 = time.time()
            self._step = step
            if self.silo_latency_hook is not None:
                # fused tiers: simulated per-silo latencies for attribution
                self.telemetry.observe_all(self.silo_latency_hook(step))
            t0 = time.time()
            with TraceAnnotation("train.dispatch"):
                if active is None:
                    # statically all-active: omit the argument so the engine
                    # traces its fixed-ring fast path (no gating/ring work —
                    # bit-identical output)
                    state, metrics = self._jit_step(state, batch, root_key)
                else:
                    state, metrics = self._jit_step(state, batch, root_key,
                                                    jnp.asarray(active))
                if self.tcfg.step_deadline_s is not None:
                    # a hard deadline needs true step time -> block per step
                    jax.block_until_ready(metrics)
            if self.tcfg.step_deadline_s is not None:
                self.straggler.observe(time.time() - t0)
            elif self.straggler.calibrated:
                # async mode: metrics stay on-device (no per-step host sync),
                # so the call returns once the step is enqueued. The enqueue
                # time still surfaces device stalls (it blocks once the
                # queue backs up), so use it for *flagging* only — the EMA
                # baseline is anchored exclusively by calibrate() at flush
                # boundaries, or the near-zero post-drain enqueue times
                # would decay it into spurious flags
                enqueue_s = time.time() - t0
                self.straggler.observe(enqueue_s, update_baseline=False)
            entry = {"step": step, **metrics}
            if self.accountant:
                with TraceAnnotation("train.ledger"):
                    # per-step participation bitmask: the ledger attributes
                    # this step's privacy loss to exactly the silos that
                    # contributed
                    self.accountant.record(active)
                    entry["epsilon"] = self.accountant.epsilon()
                    entry["epsilon_per_silo"] = \
                        self.accountant.epsilon_per_silo()
                    self._enforce_budgets(step + 1)
            self._pending.append(entry)
            step += 1
            if len(self._pending) >= max(self.tcfg.metrics_flush_every, 1):
                self._flush_metrics()
            if step % self.tcfg.checkpoint_every == 0:
                self._save(state, step)
            if self.tcfg.log_every and step % self.tcfg.log_every == 0:
                self._flush_metrics()
                last = self.metrics_log[-1]
                eps = last.get("epsilon")
                print(f"step {step:6d} loss {last['loss']:.4f} "
                      f"C {last['clip_bound']:.3f}"
                      + (f" eps {eps:.3f}" if eps is not None else ""),
                      flush=True)
        self._save(state, step)
        return state, step
