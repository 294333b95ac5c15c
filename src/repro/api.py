"""Top-level session façade: ``Session.from_config(...).train(...)/.serve(...)``.

One object owns the config → model → mesh → trainer/server wiring that the
launchers, examples and benchmarks used to re-assemble by hand:

    from repro.api import Session

    sess = Session.from_config("qwen2.5-3b",
                               privacy=PrivacyConfig(sigma=0.5, n_silos=4))
    result = sess.train(steps=50, batch_size=8, seq_len=128)
    print(result.final["loss"], result.final.get("epsilon"))

    gen = sess.serve(batch_size=4, prompt_len=32, max_new_tokens=16)
    print(gen.tokens[:2, :8])

Arch ids accept both the assignment spelling (``qwen2.5-3b``) and the
module-style spelling (``qwen25_3b``). ``Session`` is the integration point
the dispatch registry, autotuning cache and additional backends plug into;
kernel selection inside a session is still governed by
``repro.kernels.dispatch`` (``impl="auto"``; tests substitute a variant with
``force_impl``).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, replace
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config, resolve_arch
from repro.configs.base import (MeshConfig, ModelConfig, OptimizerConfig,
                                PrivacyConfig, RunConfig, ShapeConfig, SHAPES)
from repro.data.synthetic import synthetic_tokens
from repro.distributed import steps as steps_mod
from repro.models.registry import Model, build_model
from repro.runtime.trainer import Trainer, TrainerConfig


@dataclass
class TrainResult:
    """What a training run hands back: final state + the metrics history."""

    state: Any
    step: int
    metrics: list
    trainer: Trainer

    @property
    def final(self) -> dict:
        return self.metrics[-1] if self.metrics else {}


@dataclass
class ServeResult:
    """Greedy-decoded tokens + wall-clock timings."""

    tokens: np.ndarray  # (B, max_new_tokens) int32; -1-padded in scheduler mode
    prefill_s: float
    decode_s_per_token: float
    logits: Any = None  # final-step logits (B, V)
    # scheduler mode only: the ServerStats (utilization, p50/p99 latency)
    # and the completed Request objects
    stats: Any = None
    requests: Any = None


@dataclass
class Session:
    """A configured model + run wiring, ready to train or serve."""

    cfg: ModelConfig
    run_cfg: RunConfig
    model: Model
    seed: int = 0
    _last_trainer: Optional[Trainer] = None  # most recent train() wiring

    # ------------------------------------------------------------------ ctor
    @classmethod
    def from_config(cls, arch: Union[str, ModelConfig], *, full: bool = False,
                    privacy: Optional[PrivacyConfig] = None,
                    optimizer: Optional[OptimizerConfig] = None,
                    mesh: Optional[MeshConfig] = None,
                    shape: Union[str, ShapeConfig] = "train_4k",
                    compute_dtype=jnp.float32, seed: int = 0) -> "Session":
        """Build a session from an arch id (or a ready ModelConfig).

        ``full=False`` (default) loads the reduced smoke config — the full
        published configs are sized for TPU deployments and dry-run-only on
        CPU. Unspecified pieces get sensible single-host defaults: a 1-D data
        mesh over all local devices, AdamW, privacy disabled unless a
        PrivacyConfig is passed.
        """
        if isinstance(arch, ModelConfig):
            cfg = arch
        else:
            arch = resolve_arch(arch)
            cfg = get_config(arch) if full else get_smoke_config(arch)
        model = build_model(cfg, compute_dtype=compute_dtype)
        rc = RunConfig(
            model=cfg,
            shape=SHAPES[shape] if isinstance(shape, str) else shape,
            mesh=mesh or MeshConfig((jax.device_count(),), ("data",)),
            privacy=privacy if privacy is not None else PrivacyConfig(enabled=False),
            optimizer=optimizer or OptimizerConfig(),
        )
        return cls(cfg=cfg, run_cfg=rc, model=model, seed=seed)

    def with_run_config(self, **overrides) -> "Session":
        """A copy of this session with RunConfig fields replaced."""
        return replace(self, run_cfg=self.run_cfg.replace(**overrides))

    # ----------------------------------------------------------------- train
    def init_state(self, key=None):
        key = jax.random.PRNGKey(self.seed) if key is None else key
        return steps_mod.init_train_state(self.model, self.run_cfg, key)

    def trainer(self, *, total_steps: int = 50, checkpoint_dir: Optional[str] = None,
                checkpoint_every: int = 25, log_every: int = 10,
                epsilon_budget: Optional[float] = None,
                silo_epsilon_budget: Optional[float] = None,
                silo_budgets: Optional[dict] = None,
                step_deadline_s: Optional[float] = None,
                next_batch: Optional[Callable[[], dict]] = None,
                batch_size: int = 8, seq_len: int = 128,
                elastic: bool = False,
                silo_schedule: Optional[Callable[[int], Any]] = None,
                silo_latency_hook: Optional[Callable[[int], Any]] = None) -> Trainer:
        """A wired Trainer; ``next_batch`` defaults to a synthetic LM stream.

        ``elastic=True`` threads a per-step silo participation set through
        the jitted step (straggler escalations drop a silo for a cooldown
        window; the DP engine keeps the zero-sum-mask and noise-correction
        invariants over any active subset). ``silo_schedule`` pins the
        participation set deterministically: step -> bool sequence.
        ``silo_epsilon_budget`` (uniform) / ``silo_budgets`` (per-silo
        overrides) arm the privacy ledger's enforcement: an exhausted silo is
        excluded from the participation set with no rejoin until operator
        override. ``silo_latency_hook`` feeds simulated per-silo latencies to
        the straggler-attribution telemetry on the fused tiers."""
        tcfg = TrainerConfig(total_steps=total_steps,
                             checkpoint_every=checkpoint_every,
                             checkpoint_dir=checkpoint_dir,
                             log_every=log_every,
                             epsilon_budget=epsilon_budget,
                             silo_epsilon_budget=silo_epsilon_budget,
                             silo_budgets=silo_budgets,
                             step_deadline_s=step_deadline_s,
                             elastic=elastic or silo_schedule is not None)
        next_batch = next_batch or self.synthetic_batches(batch_size, seq_len)
        return Trainer(self.model, self.run_cfg, tcfg, next_batch,
                       silo_schedule=silo_schedule,
                       silo_latency_hook=silo_latency_hook)

    def train(self, *, steps: int = 50, batch_size: int = 8, seq_len: int = 128,
              next_batch: Optional[Callable[[], dict]] = None,
              checkpoint_dir: Optional[str] = None, checkpoint_every: int = 25,
              log_every: int = 10, epsilon_budget: Optional[float] = None,
              silo_epsilon_budget: Optional[float] = None,
              silo_budgets: Optional[dict] = None,
              step_deadline_s: Optional[float] = None,
              elastic: bool = False,
              silo_schedule: Optional[Callable[[int], Any]] = None,
              silo_latency_hook: Optional[Callable[[int], Any]] = None,
              state=None) -> TrainResult:
        """Run (or resume) training through the fault-tolerant Trainer loop."""
        trainer = self.trainer(total_steps=steps, checkpoint_dir=checkpoint_dir,
                               checkpoint_every=checkpoint_every,
                               log_every=log_every, epsilon_budget=epsilon_budget,
                               silo_epsilon_budget=silo_epsilon_budget,
                               silo_budgets=silo_budgets,
                               step_deadline_s=step_deadline_s,
                               next_batch=next_batch, batch_size=batch_size,
                               seq_len=seq_len, elastic=elastic,
                               silo_schedule=silo_schedule,
                               silo_latency_hook=silo_latency_hook)
        state = state if state is not None else self.init_state()
        # registered before fit so privacy_report() still surfaces the spend
        # of a run that aborts mid-way (that audit matters most then)
        self._last_trainer = trainer
        state, step = trainer.fit(state, jax.random.PRNGKey(self.seed + 1))
        return TrainResult(state=state, step=step,
                           metrics=trainer.metrics_log, trainer=trainer)

    def privacy_report(self) -> Optional[dict]:
        """The privacy ledger's spend report for the most recent ``train``
        run: per-silo epsilon over each silo's own participation history,
        budgets, remaining headroom and exclusion events. None before the
        first run (or with privacy disabled)."""
        if self._last_trainer is None:
            return None
        return self._last_trainer.spend_report()

    def synthetic_batches(self, batch_size: int, seq_len: int,
                          pool: Optional[int] = None) -> Callable[[], dict]:
        """Deterministic synthetic LM batch stream (structured token stats)."""
        toks = synthetic_tokens(pool or max(64, batch_size * 4), seq_len,
                                self.cfg.vocab_size)
        rng = np.random.default_rng(self.seed)

        def next_batch():
            idx = rng.integers(0, toks.shape[0], batch_size)
            t = jnp.asarray(toks[idx])
            return {"tokens": t[:, :-1], "labels": t[:, 1:]}

        return next_batch

    # ----------------------------------------------------------------- serve
    def serve(self, *, batch_size: int = 4, prompt_len: int = 32,
              max_new_tokens: int = 16, prompt=None, params=None,
              scheduler: Optional[str] = None, requests=None,
              max_batch: int = 8, max_len: int = 512, page_size: int = 16,
              prefill_chunk: int = 16, prefix_sharing: bool = False,
              speculative: bool = False, spec_k: int = 4,
              draft_layers: Optional[int] = None,
              tenant_weights: Optional[dict] = None) -> ServeResult:
        """Greedy decoding, three ways.

        ``scheduler=None`` (default): the direct batched prefill + decode
        path with wall-clock timings — one cache, every row in lockstep.
        ``scheduler='wave'``: the length-bucketed WaveServer baseline.
        ``scheduler='continuous'``: continuous batching over the paged,
        slot-recycled KV cache (transformer families only). Scheduler modes
        take a ``requests`` list (``runtime.serving.Request``); without one,
        ``batch_size`` uniform requests of ``prompt_len`` are synthesized.
        Both scheduler modes fill ``ServeResult.stats`` with comparable
        utilization and p50/p99 latency tails.

        Continuous-only layers (``docs/serving.md``): ``prefix_sharing``
        maps same-tenant shared prompt pages read-only (COW refcounts);
        ``speculative`` adds draft-propose/verify at ``spec_k`` tokens per
        tick (``draft_layers`` early-exit draft; None = self-draft);
        ``tenant_weights`` sets deficit-round-robin admission shares.

        ``params`` lets callers bring externally-loaded weights (e.g.
        decrypted through the KDS gate); fresh random init otherwise.
        SSM-family archs prefill recurrently (decode over the prompt).
        """
        cfg = self.cfg
        if not cfg.causal:
            raise ValueError(f"{cfg.name} is encoder-only: no decode step")
        params = params if params is not None else self.model.init(
            jax.random.PRNGKey(self.seed))
        if scheduler is not None:
            return self._serve_scheduled(
                scheduler, params, requests, batch_size=batch_size,
                prompt_len=prompt_len, max_new_tokens=max_new_tokens,
                max_batch=max_batch, max_len=max_len, page_size=page_size,
                prefill_chunk=prefill_chunk, prefix_sharing=prefix_sharing,
                speculative=speculative, spec_k=spec_k,
                draft_layers=draft_layers, tenant_weights=tenant_weights)
        if prefix_sharing or speculative or tenant_weights:
            raise ValueError("prefix_sharing/speculative/tenant_weights "
                             "need scheduler='continuous'")
        if prompt is None:
            prompt = jax.random.randint(jax.random.PRNGKey(self.seed + 1),
                                        (batch_size, prompt_len), 0,
                                        cfg.vocab_size)
        prompt = jnp.asarray(prompt)
        batch_size, prompt_len = prompt.shape
        cache = self.model.init_cache(batch_size, prompt_len + max_new_tokens)
        prefill = jax.jit(self.model.prefill)
        decode = jax.jit(self.model.decode_step)

        t0 = time.perf_counter()
        if cfg.family == "ssm":  # recurrent prefill = decode over the prompt
            for t in range(prompt_len):
                logits, cache = decode(params, {"tokens": prompt[:, t:t + 1]},
                                       cache)
        else:
            logits, cache = prefill(params, {"tokens": prompt}, cache)
        jax.block_until_ready(logits)
        prefill_s = time.perf_counter() - t0

        out = []
        tok = jnp.argmax(logits, -1)[:, None]
        t0 = time.perf_counter()
        for _ in range(max_new_tokens):
            out.append(np.asarray(tok[:, 0]))
            logits, cache = decode(params, {"tokens": tok}, cache)
            tok = jnp.argmax(logits, -1)[:, None]
        jax.block_until_ready(logits)
        decode_s = (time.perf_counter() - t0) / max(max_new_tokens, 1)

        return ServeResult(tokens=np.stack(out, 1), prefill_s=prefill_s,
                           decode_s_per_token=decode_s, logits=logits)

    def _serve_scheduled(self, scheduler: str, params, requests, *,
                         batch_size: int, prompt_len: int,
                         max_new_tokens: int, max_batch: int, max_len: int,
                         page_size: int, prefill_chunk: int,
                         prefix_sharing: bool = False,
                         speculative: bool = False, spec_k: int = 4,
                         draft_layers: Optional[int] = None,
                         tenant_weights: Optional[dict] = None) -> ServeResult:
        from repro.runtime.serving import (ContinuousServer, Request,
                                           WaveServer)

        if requests is None:
            rng = np.random.default_rng(self.seed + 1)
            requests = [Request(rid=i,
                                prompt=rng.integers(0, self.cfg.vocab_size,
                                                    prompt_len).astype(np.int32),
                                max_new_tokens=max_new_tokens)
                        for i in range(batch_size)]
        if scheduler == "wave":
            if prefix_sharing or speculative or tenant_weights:
                raise ValueError("prefix_sharing/speculative/tenant_weights "
                                 "need scheduler='continuous'")
            srv = WaveServer(self.model, params, max_batch=max_batch,
                             max_len=max_len)
        elif scheduler == "continuous":
            srv = ContinuousServer(self.model, params, max_batch=max_batch,
                                   max_len=max_len, page_size=page_size,
                                   prefill_chunk=prefill_chunk,
                                   prefix_sharing=prefix_sharing,
                                   speculative=speculative, spec_k=spec_k,
                                   draft_layers=draft_layers,
                                   tenant_weights=tenant_weights)
        else:
            raise ValueError(
                f"unknown scheduler {scheduler!r}: wave | continuous")
        for r in requests:
            srv.submit(r)
        t0 = time.perf_counter()
        stats = srv.run_until_drained()
        wall = time.perf_counter() - t0
        width = max((len(r.generated) for r in requests), default=0)
        tokens = np.full((len(requests), width), -1, np.int32)
        for i, r in enumerate(requests):
            tokens[i, :len(r.generated)] = r.generated
        return ServeResult(tokens=tokens, prefill_s=0.0,
                           decode_s_per_token=wall / max(stats.useful_tokens, 1),
                           stats=stats, requests=requests)

    # --------------------------------------------------------- introspection
    def kernel_impls(self) -> dict:
        """Registered kernel impls (priority order) — what dispatch can pick."""
        from repro import kernels

        return {k: kernels.available_impls(k)
                for k in kernels.REGISTRY.kernels()}


@dataclass
class CollaborativeSession:
    """Protocol-tier façade (paper Fig. 1): a management service, KDS and
    attested components wired for one collaborative-training session.

    ``from_silos`` performs the full setup — deploy the service, attest each
    dataset owner's data handler against the launch policy, upload + release
    per-owner channel keys through the KDS, and connect the model updater —
    so examples drive the training loop with one ``step()`` call per round.
    The updater only ever sees masked updates; the accountant composes the
    (eps, delta) budget over every round and records per-round contribution
    counts.

    Membership is elastic: ``drop_silo``/``rejoin_silo`` change who
    contributes from the next round on. The admin distributes the round's
    participation set *and* the ledger's budget verdicts with the step keys;
    each active handler builds its zero-sum mask over the ring of *active*
    silos (dp_pipeline engine — the masks still telescope to zero and the
    aggregate noise std stays exactly sigma*C for any active count), refuses
    inside the TEE boundary when its owner's budget is spent, and the
    updater divides by the actual contributors. An exhausted silo is
    excluded from membership with no rejoin until operator override
    (``rejoin_silo(..., override=True)``).
    """

    service: Any
    privacy: PrivacyConfig
    handlers: list
    updater: Any
    admin: Any
    accountant: Any  # the session's PrivacyLedger (admin-owned)
    n_silos: int
    clip_bound: float = 1.0
    membership: Any = None
    telemetry: Any = None  # per-party step-time attribution
    codec: str = "packed"  # wire codec: packed flat buffers | legacy pickle
    # Merkle batch-MAC per round (one keyed HMAC + O(log n) path per message
    # on the updater instead of n full HMAC passes; see core/tee/merkle.py)
    batch_mac: bool = False
    # delta-broadcast state: the packed buffer of the last broadcast params
    # and the broadcast epoch (handlers resync on epoch gaps)
    _bcast_buf: Any = None
    _bcast_layout: Any = None
    _bcast_epoch: int = 0
    wire_stats: Any = None  # per-session bytes-on-wire counters
    # fault-tolerance plane (docs/failure_model.md): an optional
    # FaultInjector driving seeded chaos, and the per-session counters the
    # chaos bench reports. ``_downed`` tracks silos dropped by deadline/
    # quorum closure (silo -> round it went down) for later rejoin.
    chaos: Any = None
    fault_stats: Any = None

    def __post_init__(self):
        if self.wire_stats is None:
            self.wire_stats = {"rounds": 0, "broadcast_bytes": 0,
                               "resync_bytes": 0, "update_bytes": 0}
        if self.fault_stats is None:
            self.fault_stats = {"transient_retries": 0, "kds_retries": 0,
                                "integrity_failures": [],
                                "rounds_replayed": 0, "quorum_closures": 0,
                                "deadline_hits": 0, "updater_recoveries": 0}
        self._downed: dict = {}
        self._inflight: dict = {}  # silo -> Future still running past deadline
        self._stats_lock = threading.Lock()

    @classmethod
    def from_silos(cls, silo_data: list, privacy: PrivacyConfig, *,
                   session_id: str = "session", root_seed: int = 0,
                   silo_epsilon_budget: Optional[float] = None,
                   silo_budgets: Optional[dict] = None,
                   codec: str = "packed",
                   params_template=None,
                   batch_mac: Optional[bool] = None,
                   shard_workers: Optional[int] = None,
                   received_cap: Optional[int] = None) -> "CollaborativeSession":
        """``silo_data``: one batch dict per dataset owner (stays silo-local).
        ``silo_epsilon_budget``/``silo_budgets`` arm per-owner budget
        enforcement; the ledger config joins the attestation measurement, so
        components only get keys for the enforcement terms the owners saw.

        ``codec`` selects the wire stack: ``'packed'`` (default) moves every
        round through the flat-buffer codec (raw ``(P,)`` memoryviews,
        XOR-delta params broadcast, vectorized channel crypto);
        ``'pickle'`` keeps the seed's pickle+npz blobs and per-block channel
        crypto — the benchmark baseline. ``params_template`` (a params
        pytree) pins the session's packed-layout fingerprint into the wire
        config, and therefore into every component's attestation
        measurement: a component speaking a different layout gets no keys.

        ``batch_mac`` (default: on for the packed codec) authenticates each
        round's sealed updates through the admin's Merkle batch tag — one
        keyed HMAC per round plus an O(log n) path check per message on the
        updater, with tamper of any single update still detected and
        attributed (core/tee/merkle.py). ``shard_workers`` threads the
        updater's accumulation over parameter-axis shards (bit-identical to
        the serial fold); default: 4 workers from 32 silos up, serial
        below."""
        from repro.core import flatbuf
        from repro.core.privacy import PrivacyLedger
        from repro.core.tee import wire
        from repro.core.tee.channels import (SecureChannel, VER_FAST,
                                             VER_LEGACY, derive_key)
        from repro.core.tee.components import (Admin, DataHandler,
                                               ManagementService, ModelUpdater)
        from repro.runtime.elastic import SiloMembership
        from repro.runtime.straggler import SiloTelemetry

        n = len(silo_data)
        ledger = PrivacyLedger.from_privacy_config(
            privacy, n, epsilon_budget=silo_epsilon_budget,
            budgets=silo_budgets)
        svc = ManagementService()
        wire_config = {"codec": wire.WIRE_CODEC_ID if codec == "packed"
                       else "pickle-npz-v0"}
        if params_template is not None and codec == "packed":
            wire_config["layout"] = wire.layout_fingerprint(
                flatbuf.layout_of(params_template)).hex()
        svc.create_session(session_id, n, privacy,
                           ledger_config=ledger.config_dict(),
                           wire_config=wire_config)
        chan_ver = VER_FAST if codec == "packed" else VER_LEGACY
        handlers = []
        for i, data in enumerate(silo_data):
            h = DataHandler(f"handler-{i}", svc, silo_idx=i, data=data,
                            codec=codec)
            h.attest(svc.policy)
            svc.kds.upload_key(f"dk-{i}", derive_key(b"session-root", f"dk-{i}"),
                               f"owner-{i}", svc.expected_measurement(),
                               svc.policy.hash())
            key = svc.kds.request_key(f"dk-{i}", h.report)  # released: attested OK
            h.channel = SecureChannel(key, h.name, version=chan_ver)
            handlers.append(h)
        updater = ModelUpdater("updater", svc)
        updater.attest(svc.policy)
        updater.shard_workers = shard_workers if shard_workers is not None \
            else (4 if n >= 32 else 0)
        # audit-trail bound scales with the session: at n=400 the old fixed
        # 256 silently dropped most of a single round's trail. Overflow is
        # counted in updater.truncated_entries either way.
        updater.received_cap = received_cap if received_cap is not None \
            else max(256, 2 * n)
        for h in handlers:
            updater.channels[h.name] = SecureChannel(
                svc.kds._records[f"dk-{h.silo_idx}"].key, h.name,
                version=chan_ver)

        admin = Admin("admin", svc, root_key=jax.random.PRNGKey(root_seed),
                      n_silos=n, ledger=ledger)
        admin.attest(svc.policy)  # signs spend reports with this identity
        # admin<->updater aggregation key for the Merkle batch tags: the
        # model owner uploads it, the KDS releases it only against BOTH
        # components' verified measurements — a driver between them cannot
        # mint tags
        svc.kds.upload_key("dk-agg", derive_key(b"session-root", "dk-agg"),
                           "model-owner", svc.expected_measurement(),
                           svc.policy.hash())
        admin.agg_key = svc.kds.request_key("dk-agg", admin.report)
        updater.agg_key = svc.kds.request_key("dk-agg", updater.report)
        for h in handlers:
            # handlers trust the attested admin for budget verdicts — the
            # training driver can't fabricate an all-allowed vector
            h.admin = admin
        return cls(service=svc, privacy=privacy, handlers=handlers,
                   updater=updater, admin=admin, accountant=ledger,
                   n_silos=n, clip_bound=privacy.clip_bound,
                   membership=SiloMembership(n),
                   telemetry=SiloTelemetry(n), codec=codec,
                   batch_mac=batch_mac if batch_mac is not None
                   else codec == "packed")

    def drop_silo(self, silo: int, step: Optional[int] = None,
                  cooldown: Optional[int] = None) -> bool:
        """Remove a dataset owner from the next rounds (returns False when
        the quorum would be broken). ``step`` defaults to the next round, so
        a mid-session cooldown counts from now rather than from round 0."""
        step = self._next_round if step is None else step
        return self.membership.drop(silo, step, cooldown)

    def drop_slowest(self, step: Optional[int] = None,
                     cooldown: Optional[int] = None) -> Optional[int]:
        """Straggler escalation with real attribution: drop the silo whose
        handler round-trips have been slowest (per-party timing recorded by
        :meth:`step`)."""
        step = self._next_round if step is None else step
        return self.membership.drop_one(step, cooldown,
                                        telemetry=self.telemetry)

    def rejoin_silo(self, silo: int, step: Optional[int] = None,
                    override: bool = False) -> bool:
        """Budget-excluded owners only rejoin with ``override=True`` (the
        operator decision after e.g. a fresh budget grant)."""
        return self.membership.rejoin(
            silo, step=self._next_round if step is None else step,
            override=override)

    def rejoin_silo_async(self, silo: int, override: bool = False) -> bool:
        """Mid-round rejoin: the dropped owner's handler re-attests, gets its
        channel key re-released through the KDS and is warm-resynced to the
        *current* params epoch NOW — while the in-flight round keeps running
        without it — then enters the participation set at the next round
        start. Contrast with :meth:`rejoin_silo`, which only flips membership
        and leaves the handler to hit :class:`StaleParamsError` (and pay a
        blocking full resync) inside its first round back. The warm resync
        rides the same epoch-tagged wire path, so a handler that somehow
        missed it still degrades to the in-round resync rather than applying
        a stale delta.

        Failure discipline (docs/failure_model.md): a transient KDS denial
        (:class:`~repro.core.tee.faults.KdsTransientDenial`) is retried with
        deterministic-jitter exponential backoff; an attestation
        ``PermissionError`` is an integrity failure and propagates
        immediately. Membership flips LAST — after attestation and key
        release succeed — so any failure leaves membership untouched
        (fail closed, flip exactly once on success)."""
        from repro.core.tee.channels import SecureChannel, VER_FAST, VER_LEGACY
        from repro.core.tee.faults import Backoff, KdsTransientDenial

        if silo in self.membership.excluded and not override:
            # budget-excluded: refuse BEFORE attesting or touching the KDS
            # (membership.rejoin records the refusal event, mutates nothing)
            return self.membership.rejoin(silo, step=self._next_round,
                                          override=False)
        h = self.handlers[silo]
        # fresh attestation against the live policy: a handler whose
        # measurement drifted while it was out gets no key, and therefore
        # no channel — the rejoin fails closed
        h.attest(self.service.policy)
        backoff = Backoff(seed=silo)
        while True:
            try:
                key = self.service.kds.request_key(f"dk-{silo}", h.report)
                break
            except KdsTransientDenial:
                # transient release hiccup: retry with backoff. A
                # PermissionError (measurement/policy mismatch) is an
                # integrity failure — it propagates, membership untouched.
                with self._stats_lock:
                    self.fault_stats["kds_retries"] += 1
                if not backoff.sleep():
                    raise
        if not self.membership.rejoin(silo, step=self._next_round,
                                      override=override):
            return False
        ver = VER_FAST if self.codec == "packed" else VER_LEGACY
        # both channel ends are rebuilt so the replay counters restart in
        # sync (the dropped handler's old counters are gone with its session)
        h.channel = SecureChannel(key, h.name, version=ver)
        self.updater.channels[h.name] = SecureChannel(key, h.name, version=ver)
        if self._bcast_buf is not None:
            # warm resync at the current epoch: the next round's delta
            # broadcast (epoch + 1) chains cleanly instead of raising
            # StaleParamsError on the round's critical path
            blob = self._resync_blob()
            self.wire_stats["resync_bytes"] += len(blob)
            h._sync_params(blob)
        return True

    @property
    def _next_round(self) -> int:
        return self.accountant.steps

    # ------------------------------------------------------------- wire plane
    def _admin_plane(self, step_idx: int) -> dict:
        """Round-(t) admin fanout: step keys, budget verdicts, budget-driven
        membership exclusions, the resolved participation set and the
        noise-correction state — everything the handlers need before they
        can compute. Factored out so :meth:`run` can overlap round t+1's
        fanout with round t's aggregation."""
        keys = self.admin.keys_for_step(step_idx)
        verdicts = self.admin.verdicts()
        for silo in self.accountant.take_exclusions():
            # budget-driven membership drop: no rejoin without override
            self.membership.exclude(silo, step=step_idx, reason="budget")
        active = self.membership.active_at(step_idx) & verdicts
        return {"step": step_idx, "keys": keys, "verdicts": verdicts,
                "active": active, "noise_state": self.admin.state_for_step()}

    def _params_broadcast(self, params):
        """Encode this round's params distribution ONCE. Packed codec: the
        XOR delta of the packed buffer against the previous broadcast (a
        full message only on the first round or a layout change) — one
        broadcast for all handlers instead of a params blob per handler.
        Pickle codec (baseline): the legacy full pytree blob, unicast
        per handler. Returns (blob, is_broadcast)."""
        from repro.core import flatbuf
        from repro.core.tee import wire
        from repro.core.tee.components import _ser

        if self.codec != "packed" or not wire.packable(params):
            return _ser(params, codec="pickle"), False
        layout = flatbuf.layout_of(params)
        new_buf = wire.pack_np(layout, params)
        self._bcast_epoch += 1
        if self._bcast_buf is None or self._bcast_layout is not layout:
            blob = wire.encode_full(layout, new_buf, epoch=self._bcast_epoch)
        else:
            blob = wire.encode_delta(layout, self._bcast_buf, new_buf,
                                     epoch=self._bcast_epoch)
        self._bcast_buf, self._bcast_layout = new_buf, layout
        return blob, True

    def _resync_blob(self) -> bytes:
        """Full packed params at the current epoch — the unicast a handler
        that missed rounds (drop/rejoin) gets when its delta chain broke."""
        from repro.core.tee import wire
        return wire.encode_full(self._bcast_layout, self._bcast_buf,
                                epoch=self._bcast_epoch)

    def _compute_one(self, h, blob: bytes, plan: dict, grad_fn: Callable,
                     admin_row) -> bytes:
        """One handler's round-trip: compute_update with the in-round
        StaleParamsError -> full-resync retry, per-party timing into the
        straggler telemetry, update bytes into the wire counters. Shared by
        the serial collect loop and the deadline/quorum tolerant collect."""
        from repro.core.tee import wire

        active = plan["active"]
        t0 = time.perf_counter()
        try:
            u = h.compute_update(blob, grad_fn, self.privacy,
                                 plan["keys"], self.n_silos,
                                 clip_bound=self.clip_bound,
                                 active=active,
                                 noise_state=plan["noise_state"],
                                 verdicts=plan["verdicts"],
                                 admin_row=admin_row)
        except wire.StaleParamsError:
            with self._stats_lock:
                full = self._resync_blob()
                self.wire_stats["resync_bytes"] += len(full)
            u = h.compute_update(full, grad_fn, self.privacy,
                                 plan["keys"], self.n_silos,
                                 clip_bound=self.clip_bound,
                                 active=active,
                                 noise_state=plan["noise_state"],
                                 verdicts=plan["verdicts"],
                                 admin_row=admin_row)
        with self._stats_lock:
            # real per-party timing feeds straggler attribution
            self.telemetry.observe(h.silo_idx, time.perf_counter() - t0)
            self.wire_stats["update_bytes"] += len(u)
        return u

    def _collect_updates(self, params, plan: dict, grad_fn: Callable,
                         sink: Optional[Callable] = None) -> dict:
        """Distribute params + keys to the round's active handlers and
        collect their sealed masked updates (per-party round-trip timing
        feeds straggler attribution). A handler whose delta chain broke
        raises StaleParamsError in-TEE and is resynced with a full blob.
        ``sink(name, blob)`` streams each update out as it is produced (the
        pipelined runner feeds the updater's ingestion thread with it)."""
        from repro.core.tee import wire

        blob, is_bcast = self._params_broadcast(params)
        active = plan["active"]
        if is_bcast:
            # a broadcast medium carries the delta once, not per handler
            self.wire_stats["broadcast_bytes"] += len(blob)
        else:
            self.wire_stats["broadcast_bytes"] += \
                len(blob) * int(np.sum(active))
        # admin-mode masking: the closing row is computed ONCE on the admin
        # and handed to the one closing handler — O(P) fan-out per round at
        # any n, instead of that handler regenerating all n rows (an (n, P)
        # stack) to reconstruct the zero-sum closer
        admin_row = None
        if self.privacy.enabled and self.privacy.mask_mode == "admin" \
                and bool(np.any(active)):
            admin_row = self.admin.closing_mask_row(
                self.privacy, params, plan["keys"], active,
                plan["noise_state"], self.clip_bound)
        handlers = [h for h in self.handlers if active[h.silo_idx]]

        def one(h):
            u = self._compute_one(h, blob, plan, grad_fn, admin_row)
            if sink is not None:
                sink(h.name, u)
            return u

        # each handler's numerics are keyed by its silo index, so execution
        # order cannot change any value; results are assembled in silo
        # order regardless of how a driver schedules the parties (the
        # updater's expected-order staging covers out-of-order delivery)
        results = [one(h) for h in handlers]
        updates = {h.name: u for h, u in zip(handlers, results)}
        if not updates:
            raise RuntimeError(
                "no silo may contribute this round (budgets exhausted or "
                "membership empty); DP forbids further training")
        return updates

    def _batch_tag(self, round_id: int, updates: dict) -> Optional[dict]:
        """The round's Merkle batch tag over the sealed updates, in the
        order they were produced (each handler reported its leaf — the
        digest of its whole channel blob — when it sealed; see
        ``DataHandler.compute_update``). None when batch-MAC is off: the
        updater then runs per-message HMAC as before."""
        if not self.batch_mac:
            return None
        by_name = {h.name: h for h in self.handlers}
        return self.admin.batch_tag(
            [(name, by_name[name].last_leaf) for name in updates], round_id)

    def step(self, step_idx: int, params, grad_fn: Callable,
             update_fn: Callable, lr: float):
        """One round: admin keys + participation set + budget verdicts +
        correction state -> active silo updates (clip + zero-sum DP mask over
        the active ring, model-owner code sandboxed; handlers with a spent
        budget refuse in-TEE) -> updater aggregate over the actual
        contributors -> admin advances the correction state and the ledger
        records the round's participation bitmask. Returns
        (new_params, mean_loss)."""
        plan = self._admin_plane(step_idx)
        updates = self._collect_updates(params, plan, grad_fn)
        params, loss = self.updater.aggregate(
            updates, params, update_fn, lr=lr,
            batch=self._batch_tag(step_idx, updates))
        self.admin.advance(plan["keys"], plan["active"])  # ledger bitmask
        self.wire_stats["rounds"] += 1
        return params, loss

    def run(self, params, grad_fn: Callable, update_fn: Callable, lr: float,
            n_rounds: int, pipelined: bool = True,
            speculative: bool = False,
            round_timeout_s: Optional[float] = None,
            quorum: Optional[int] = None,
            chaos: Any = None,
            journal: Any = None,
            rejoin_after: Optional[int] = 2):
        """Drive ``n_rounds`` of the protocol. ``pipelined=True`` streams
        each handler's sealed update into the updater's ingestion thread as
        soon as it is produced (decrypt + decode + accumulate of silo i
        overlaps silo i+1's compute; a single worker preserves silo order,
        so the sum's fp association — part of the cross-tier bit-parity
        contract — is unchanged), and overlaps the admin plane — round t's
        ledger write plus round t+1's key fanout, verdict distribution and
        correction-state rollout — with the tail of the aggregation. The
        updater and admin are separate trust domains with disjoint state, so
        the overlap changes nothing about the math — bit-identical to the
        serial loop. Per-party handler timings stay honest: each handler
        round-trip is measured synchronously, as in :meth:`step`.

        ``speculative=True`` (implies pipelined) additionally lets handlers
        begin round t+1's noise-stream work while round t's aggregation and
        broadcast are still in flight, and — the structural win — reuse
        round t's xi stream as round t+1's lambda-correction stream (the
        admin's schedule makes them the same stream: ``advance`` sets
        ``prev_key = raw(key_xi)``), eliminating one full P-length draw per
        handler per round. Every speculated artifact is tagged with the raw
        key bytes it was drawn under and consumed only on an exact tag
        match, with cache misses falling back to an inline draw through the
        same jit — so rekeys, resyncs (``StaleParamsError`` → full resync,
        exactly the epoch-tag guard of the delta broadcast) and mid-round
        membership changes degrade to the serial path rather than diverging.
        Speculative rounds are bit-identical to serial :meth:`step` loops.
        Returns (params, [per-round mean losses]).

        Fault-tolerant mode (``round_timeout_s``/``quorum``/``chaos``/
        ``journal`` — docs/failure_model.md): handlers are dispatched
        concurrently; the round closes once a quorum of expected updates has
        landed and the deadline has expired. Non-responders are routed
        through the elastic machinery (``SiloMembership`` drop + active-set
        shrink + ledger recording only actual contributors) and the round is
        REPLAYED over the realized set — it then literally IS a scheduled
        elastic round, so a quorum-closed round is bit-identical to a
        fault-free elastic run with the same participation sets. Transient
        faults (dropped blob, KDS denial, stale params) retry with
        deterministic-jitter backoff; integrity failures (bad MAC, Merkle
        leaf mismatch) are never retried — the tainted aggregate is
        discarded, the silo attributed and dropped. ``chaos`` takes a
        :class:`~repro.core.tee.faults.FaultInjector`; ``journal`` a
        :class:`~repro.core.tee.faults.RoundJournal` (each committed round
        is journaled; an updater crash between ingest and finish_round
        discards the partial round and replays it bit-exactly; after a
        driver restart :meth:`resume` continues from the journal).
        ``rejoin_after``: rounds a dropped silo sits out before the session
        re-admits it through :meth:`rejoin_silo_async` (re-attest, KDS
        re-release with backoff, warm resync); None = never."""
        if round_timeout_s is not None or chaos is not None \
                or journal is not None or quorum is not None:
            if chaos is not None:
                self.chaos = chaos
            return self._run_tolerant(params, grad_fn, update_fn, lr,
                                      n_rounds, round_timeout_s, quorum,
                                      journal, rejoin_after)
        if speculative:
            pipelined = True
        spec_flags = [h.speculative for h in self.handlers]
        if speculative:
            for h in self.handlers:
                h.speculative = True
        try:
            return self._run(params, grad_fn, update_fn, lr, n_rounds,
                             pipelined, speculative)
        finally:
            for h, f in zip(self.handlers, spec_flags):
                h.speculative = f

    def _run(self, params, grad_fn: Callable, update_fn: Callable, lr: float,
             n_rounds: int, pipelined: bool, speculative: bool):
        from concurrent.futures import ThreadPoolExecutor

        losses = []
        start = self._next_round
        if not pipelined:
            for t in range(start, start + n_rounds):
                params, loss = self.step(t, params, grad_fn, update_fn, lr)
                losses.append(loss)
            return params, losses
        with ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="updater") as ex:
            plan = self._admin_plane(start)
            for t in range(start, start + n_rounds):
                # batch-MAC mode: updates stream into the updater BEFORE the
                # admin has seen every leaf, so the tag is issued after the
                # last ingest and verified in finish_round — nothing commits
                # until every leaf sits under the MACed root. The expected
                # order makes the updater stage out-of-order arrivals (the
                # party pool completes in any order) and flush in silo
                # order: the sum's fp association stays bit-identical
                expected = [h.name for h in self.handlers
                            if plan["active"][h.silo_idx]]
                rs = self.updater.begin_round(params, expected=expected,
                                              batch_mode=self.batch_mac)
                ingests = []

                def sink(name, blob):
                    # fail fast: if an earlier ingest already died on the
                    # updater thread, abort the collection NOW with that
                    # error (chained, so the thread's traceback survives)
                    # instead of computing the remaining handlers' updates
                    # against a round that can no longer commit
                    for ing in ingests:
                        if ing.done() and ing.exception() is not None:
                            raise RuntimeError(
                                f"updater ingestion thread failed mid-round "
                                f"(before {name}'s update was submitted)"
                            ) from ing.exception()
                    ingests.append(
                        ex.submit(self.updater.ingest, rs, name, blob))

                updates = self._collect_updates(params, plan, grad_fn,
                                                sink=sink)
                for ing in ingests:
                    # decode/auth errors surface BEFORE the admin plane
                    # advances — same failure behaviour as the serial loop
                    ing.result()
                fut = ex.submit(self.updater.finish_round, rs, update_fn,
                                lr, self._batch_tag(t, updates))
                # overlapped with the aggregation tail running above. If the
                # model owner's update_fn itself fails, this round is already
                # recorded — conservative: the handlers' masked updates left
                # the TEEs, so the privacy loss was genuinely incurred
                self.admin.advance(plan["keys"], plan["active"])
                self.wire_stats["rounds"] += 1
                next_plan = self._admin_plane(t + 1) \
                    if t + 1 < start + n_rounds else None
                if speculative and next_plan is not None:
                    # round t+1's xi streams drawn while round t's aggregate
                    # + broadcast tail is still in the updater thread; the
                    # key-tag cache makes a wrong guess a harmless miss
                    for h in self.handlers:
                        if next_plan["active"][h.silo_idx]:
                            h.prefetch_round(next_plan["keys"])
                params, loss = fut.result()
                losses.append(loss)
                plan = next_plan
        return params, losses

    # ------------------------------------------------ fault-tolerant rounds
    def _run_tolerant(self, params, grad_fn: Callable, update_fn: Callable,
                      lr: float, n_rounds: int,
                      round_timeout_s: Optional[float],
                      quorum: Optional[int], journal, rejoin_after):
        from concurrent.futures import ThreadPoolExecutor
        from repro.core.tee import wire
        from repro.core.tee.faults import RoundJournal

        journal = journal if journal is not None else RoundJournal()
        if self.chaos is not None:
            self.service.kds.fault_hook = self.chaos.kds_fault
        losses = []
        start = self._next_round
        old_min = self.membership.min_active
        if quorum is not None:
            # the membership quorum and the round-closure quorum are the
            # same number: a drop that would leave fewer silos is refused
            self.membership.min_active = max(quorum, 1)
        ex = ThreadPoolExecutor(max_workers=max(self.n_silos, 1),
                                thread_name_prefix="collect")
        try:
            for t in range(start, start + n_rounds):
                self._rejoin_downed(t, rejoin_after)
                params, loss, active = self._step_tolerant(
                    t, params, grad_fn, update_fn, lr, round_timeout_s,
                    quorum, ex)
                losses.append(loss)
                journal.commit(t, active, wire.encode_tree(params),
                               downed=self._downed)
        finally:
            self.membership.min_active = old_min
            if self.chaos is not None:
                self.service.kds.fault_hook = None
            # waits for any still-hung workers (bounded by the injected
            # hang durations); their late results are discarded
            ex.shutdown(wait=True)
            self._inflight.clear()
        return params, losses

    def _rejoin_downed(self, t: int, rejoin_after: Optional[int]) -> None:
        """Re-admit silos dropped by deadline/quorum closure once they have
        sat out ``rejoin_after`` rounds — through the full async-rejoin path
        (fresh attestation, KDS re-release with transient-denial backoff,
        channel rebuild, warm resync). A silo whose hung worker is still
        running is skipped until it resolves (its handler state must not be
        touched concurrently)."""
        if rejoin_after is None:
            return
        for silo in sorted(self._downed):
            if t - self._downed[silo] < rejoin_after:
                continue
            fut = self._inflight.get(silo)
            if fut is not None and not fut.done():
                continue
            self._inflight.pop(silo, None)
            if self.chaos is not None:
                self.chaos.arm_kds(t)
            if self.rejoin_silo_async(silo):
                del self._downed[silo]

    def _step_tolerant(self, t: int, params, grad_fn: Callable,
                       update_fn: Callable, lr: float,
                       round_timeout_s: Optional[float],
                       quorum: Optional[int], ex):
        """One deadline/quorum round, replayed until it commits.

        Each attempt resolves the plan over the CURRENT membership, collects
        concurrently under the deadline, and either (a) commits — every
        expected silo responded and every update authenticated — or (b)
        shrinks membership (non-responders dropped with timeout attribution;
        integrity offenders attributed and dropped, their updates never
        retried) and replays. The replay recomputes every contribution and
        the admin-mode closing row over the realized set, so the committed
        round is bit-identical to a scheduled elastic round with that active
        set. Injected faults are one-shot, so replays converge; the attempt
        bound only guards against a genuinely wedged deployment."""
        from repro.core.tee import wire
        from repro.core.tee.faults import UpdaterCrashError

        for _attempt in range(2 * self.n_silos + 4):
            plan = self._admin_plane(t)
            active = plan["active"]
            n_active = int(np.sum(active))
            if n_active == 0:
                raise RuntimeError(
                    "no silo may contribute this round (budgets exhausted "
                    "or membership empty); DP forbids further training")
            q = n_active if quorum is None else min(quorum, n_active)
            responders, nonresponders = self._collect_tolerant(
                params, plan, grad_fn, round_timeout_s, q, t, ex)
            if nonresponders:
                with self._stats_lock:
                    self.fault_stats["quorum_closures"] += 1
                    self.fault_stats["rounds_replayed"] += 1
                for silo in nonresponders:
                    if self.membership.drop(silo, step=t):
                        self._downed[silo] = t
                    if round_timeout_s:
                        self.telemetry.penalize(silo, round_timeout_s)
                continue  # replay over the realized set
            # full expected set responded: aggregate with per-silo
            # attribution. The tag is built from the leaves each worker
            # digested at PRODUCTION time (not handler.last_leaf, which a
            # late hung worker could clobber), so corruption in transit
            # shows up as a leaf/path mismatch at ingest — attributed.
            names = [h.name for h in self.handlers if active[h.silo_idx]]
            batch = self.admin.batch_tag(
                [(n, responders[n][1]) for n in names], t) \
                if self.batch_mac else None
            rs = self.updater.begin_round(params, expected=names,
                                          batch=batch)
            bad = []
            for name in names:
                try:
                    self.updater.ingest(rs, name, responders[name][0])
                except (wire.WireFormatError, ValueError) as e:
                    bad.append((name, e))
            if bad:
                # integrity: fail closed — never retry these updates, drop
                # and attribute the offenders, discard the aggregate
                with self._stats_lock:
                    for name, e in bad:
                        self.fault_stats["integrity_failures"].append(
                            {"round": t, "silo": name, "error": str(e)})
                    self.fault_stats["rounds_replayed"] += 1
                by_name = {h.name: h.silo_idx for h in self.handlers}
                for name, _ in bad:
                    if self.membership.drop(by_name[name], step=t):
                        self._downed[by_name[name]] = t
                continue
            if self.chaos is not None:
                self.updater.fault_hook = \
                    lambda _t=t: self.chaos.updater_fault(_t)
            try:
                new_params, loss = self.updater.finish_round(
                    rs, update_fn, lr, batch)
            except UpdaterCrashError:
                # crash between ingest and finish: the partial round is
                # discarded (nothing committed, nothing journaled) and the
                # whole round replays — round-keyed streams make the replay
                # bit-exact
                with self._stats_lock:
                    self.fault_stats["updater_recoveries"] += 1
                    self.fault_stats["rounds_replayed"] += 1
                continue
            finally:
                self.updater.fault_hook = None
            self.admin.advance(plan["keys"], plan["active"])
            with self._stats_lock:
                self.wire_stats["rounds"] += 1
            return new_params, loss, np.asarray(plan["active"], bool)
        raise RuntimeError(
            f"round {t} failed to close after {2 * self.n_silos + 4} "
            f"attempts (persistent faults beyond the chaos model)")

    def _collect_tolerant(self, params, plan: dict, grad_fn: Callable,
                          round_timeout_s: Optional[float], q: int, t: int,
                          ex):
        """Concurrent collect under a deadline: every expected handler is
        dispatched at once; after ``round_timeout_s`` the round closes if at
        least ``q`` responders have landed (otherwise it keeps waiting until
        quorum or until every worker resolves — closing below quorum would
        break the DP participation floor). Returns ``(responders,
        nonresponders)``: responders maps handler name -> (delivered sealed
        blob, production-time leaf digest); nonresponders lists silo indices
        that crashed or are still hung — their workers keep running
        detached and their eventual results are discarded."""
        import hashlib
        from concurrent.futures import wait
        from repro.core.tee.faults import Backoff, SiloCrashError

        blob, is_bcast = self._params_broadcast(params)
        active = plan["active"]
        with self._stats_lock:
            self.wire_stats["broadcast_bytes"] += len(blob) if is_bcast \
                else len(blob) * int(np.sum(active))
        admin_row = None
        if self.privacy.enabled and self.privacy.mask_mode == "admin" \
                and bool(np.any(active)):
            admin_row = self.admin.closing_mask_row(
                self.privacy, params, plan["keys"], active,
                plan["noise_state"], self.clip_bound)
        handlers = [h for h in self.handlers if active[h.silo_idx]]
        chaos = self.chaos

        def worker(h):
            if chaos is not None:
                h.fault_hook = lambda silo, _t=t: chaos.handler_fault(_t,
                                                                      silo)
            try:
                u = self._compute_one(h, blob, plan, grad_fn, admin_row)
            finally:
                h.fault_hook = None
            leaf = hashlib.sha256(u).digest()
            delivered = u
            if chaos is not None:
                delivered = chaos.transit_fault(t, h.silo_idx, u)
                if delivered is None:
                    # transient DROP: the blob never arrived; the sender's
                    # retransmit buffer re-delivers the SAME sealed blob
                    # after backoff (the channel's monotone counter admits a
                    # first delivery at any value — this is not a replay)
                    with self._stats_lock:
                        self.fault_stats["transient_retries"] += 1
                    Backoff(seed=t * 1009 + h.silo_idx).sleep()
                    delivered = u
            return h.name, delivered, leaf

        futs = {ex.submit(worker, h): h for h in handlers}
        done, pending = wait(set(futs), timeout=round_timeout_s)
        if pending:
            with self._stats_lock:
                self.fault_stats["deadline_hits"] += 1
        while pending and \
                sum(1 for f in done if f.exception() is None) < q:
            d2, pending = wait(pending, timeout=0.02)
            done |= d2
        responders, nonresponders = {}, []
        for f in done:
            exc = f.exception()
            if exc is None:
                name, delivered, leaf = f.result()
                responders[name] = (delivered, leaf)
            elif isinstance(exc, SiloCrashError):
                nonresponders.append(futs[f].silo_idx)
            else:
                raise exc
        for f in pending:  # hung past the deadline with quorum met
            silo = futs[f].silo_idx
            nonresponders.append(silo)
            self._inflight[silo] = f
        return responders, nonresponders

    def resume(self, journal):
        """Continue from a :class:`~repro.core.tee.faults.RoundJournal`
        after a driver restart: replay each committed round's participation
        bitmask through the admin (rolling the noise-correction state and
        the ledger — contributions, steps and budget verdicts all land
        exactly where the crashed driver left them), re-drop the journaled
        downed silos, and return the journaled params (None for an empty
        journal). The next :meth:`run` call then starts at the correct round
        index with a fresh FULL broadcast — bit-identical from there on to a
        driver that never died, because every stream is keyed by the round
        index."""
        from repro.core.tee import wire

        for rec in journal.rounds:
            keys = self.admin.keys_for_step(rec["round"])
            self.admin.advance(keys, np.asarray(rec["active"], bool))
        nxt = journal.rounds[-1]["round"] + 1 if journal.rounds else 0
        for silo, rnd in journal.downed.items():
            if self.membership.drop(int(silo), step=nxt):
                self._downed[int(silo)] = int(rnd)
        return wire.decode_tree(journal.params_blob) \
            if journal.params_blob is not None else None

    def epsilon(self, silo: Optional[int] = None) -> float:
        """Spent epsilon — global, or silo-specific over that owner's own
        participation history."""
        return self.accountant.epsilon(silo)

    def privacy_report(self) -> dict:
        """The admin-plane spend report (per-silo epsilon/budgets/verdicts,
        plus each silo's observed round-trip EMA), HMAC-signed with a key
        derived from the admin's attestation identity (verify with
        ``repro.analysis.report.verify_spend_report``)."""
        rt = self.telemetry.snapshot()
        if getattr(self.admin, "ledger", None) is not None:
            return self.admin.sign_spend_report(round_trip_s=rt)
        return self.accountant.spend_report(round_trip_s=rt)

    @property
    def expected_measurement(self) -> str:
        return self.service.expected_measurement()


def train(arch: str, **kw) -> TrainResult:
    """One-call convenience: ``repro.api.train("qwen2.5-3b", steps=10)``.

    Session.from_config kwargs (full/privacy/optimizer/mesh/shape/seed) are
    split off automatically; the rest go to :meth:`Session.train`.
    """
    ctor_keys = ("full", "privacy", "optimizer", "mesh", "shape",
                 "compute_dtype", "seed")
    ctor = {k: kw.pop(k) for k in ctor_keys if k in kw}
    return Session.from_config(arch, **ctor).train(**kw)


def serve(arch: str, **kw) -> ServeResult:
    """One-call convenience: ``repro.api.serve("qwen2.5-3b", max_new_tokens=8)``."""
    ctor_keys = ("full", "privacy", "optimizer", "mesh", "shape",
                 "compute_dtype", "seed")
    ctor = {k: kw.pop(k) for k in ctor_keys if k in kw}
    return Session.from_config(arch, **ctor).serve(**kw)
