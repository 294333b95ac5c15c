"""The CITADEL++ component protocol (paper §3.2-3.3), run end-to-end
in-process: management service, KDS, admin / data-handling / model-updating
components, each in its own simulated trust domain.

This is the *wire-protocol* tier (small/paper models; serialized, encrypted
payloads between components). The SPMD tier (distributed/steps.py) implements
the same math in one jitted graph for pod-scale runs; tests assert the two
tiers agree.

Workflow (paper Fig. 1):
  1. owners encrypt assets -> untrusted storage
  2-3. owners attest KDS, upload keys + training config
  4-5. management service deploys components (admin, updater, handlers)
  6-7. components register, fetch encrypted assets, attest to KDS, get keys
  loop: admin distributes per-step mask keys -> handlers compute clipped,
        DP-masked gradients (model-owner code inside the sandbox) -> updater
        aggregates (sees only masked updates) -> admin advances
"""
from __future__ import annotations

import hashlib
import hmac
import queue
import struct
import threading
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import PrivacyConfig
from repro.core import dp_pipeline, flatbuf
from repro.core.privacy import PrivacyLedger
from repro.core.barrier import BarrierKeys, step_keys
from repro.core.dp_pipeline import DPPipeline
from repro.core.noise_correction import NoiseState, init_state
from repro.core.tee import merkle, wire
from repro.core.tee.attestation import (AttestationService, LaunchPolicy,
                                        measure_config, measure_modules)
from repro.core.tee.channels import (SecureChannel, derive_key, open_sealed,
                                     seal, spend_report_mac)
from repro.core.tee.kds import KeyDistributionService
from repro.core.tee.sandbox import Sandbox


def _ser(tree, codec: str = "packed") -> bytes:
    """Serialize a pytree for the wire: packed flat-buffer codec when
    lossless, legacy pickle+npz fallback otherwise (see core/tee/wire.py)."""
    return wire.encode_tree(tree, codec=codec)


def _deser(blob: bytes):
    return wire.decode_tree(blob)


def _guarded_modules():
    """The service code whose measurement the KDS gates key release on: the
    DP engine, the privacy ledger (budget enforcement is part of the trusted
    computing base — malicious training code must not be able to swap it
    out), the packed-buffer layout + wire codec (a component speaking a
    different wire format is a different component) and the kernel-level
    pieces they compose."""
    import repro.core.barrier as _b
    import repro.core.clipping as _c
    import repro.core.dp_pipeline as _p
    import repro.core.flatbuf as _f
    import repro.core.masking as _m
    import repro.core.privacy.bounds as _pb
    import repro.core.privacy.ledger as _pl
    import repro.core.tee.merkle as _mk
    import repro.core.tee.wire as _w
    return [_p, _pl, _pb, _b, _c, _m, _f, _w, _mk]


def _bind_configs(code: str, ledger_config: dict, wire_config: dict) -> str:
    """Extend the code measurement with the session's launch configuration:
    per-silo budgets (what the owners agreed to enforce) and the wire codec
    identity (optionally pinned to the session's packed-layout fingerprint).
    A service launched with different parameters measures differently and
    the KDS withholds keys."""
    if not ledger_config and not wire_config:
        return code
    cfg = {"ledger": ledger_config, "wire": wire_config}
    return hashlib.sha256((code + measure_config(cfg)).encode()).hexdigest()


# ---------------------------------------------------------------------------
# Shared jitted handler pipeline (many-silo scale-out)
#
# Handlers used to jit their norm->clip->mask pipeline with their silo index
# baked in as a closure constant — n separate XLA compiles per session, which
# at n=400 dominates setup and bloats the jit cache. The packed engine
# already supports a *traced* silo index (the barrier tier passes
# lax.axis_index), so one compile keyed on the engine configuration serves
# every handler, with silo as a runtime argument. PrivacyConfig is a plain
# (unhashable) dataclass, so the cache is a small equality-scan list rather
# than a dict.

_PIPE_CACHE: list = []  # [(key, jitted_fn)]
_PIPE_CACHE_MAX = 32
_PIPE_CACHE_LOCK = threading.Lock()


def _shared_pipe_fn(pipe: DPPipeline, has_prev_active: bool,
                    ext: str = "none"):
    """``ext`` selects how the pairwise noise streams enter the graph:
    ``'none'`` draws them in-graph (mask_mode 'none' / legacy callers);
    ``'xi'`` / ``'xi+xp'`` take them as ARGUMENTS, drawn by the standalone
    :meth:`DPPipeline.noise_stream` jit. The packed pairwise handler path
    always uses the external form — serial and speculative rounds then run
    the SAME compiled graph on the same stream values (cache hit or inline
    redraw are the same jit's output), so speculative==serial bit-identity
    holds by construction rather than by hoping two different XLA graphs
    fuse identically."""
    key = (pipe.priv, pipe.layout, pipe.n_silos, has_prev_active, ext)
    with _PIPE_CACHE_LOCK:
        for k, fn in _PIPE_CACHE:
            if k == key:
                return fn

    if ext == "xi+xp":
        def fn(g, silo, active, keys, state, bound, xi, xp):
            norm = pipe.norm_tree(g)
            scale = pipe.clip_scale(norm, bound)
            return pipe.silo_contribution(g, silo, scale, active, keys,
                                          state, bound, xi=xi, xp=xp), norm
    elif ext == "xi":
        def fn(g, silo, active, keys, state, bound, xi):
            norm = pipe.norm_tree(g)
            scale = pipe.clip_scale(norm, bound)
            return pipe.silo_contribution(g, silo, scale, active, keys,
                                          state, bound, xi=xi), norm
    else:
        def fn(g, silo, active, keys, state, bound):
            norm = pipe.norm_tree(g)
            scale = pipe.clip_scale(norm, bound)
            return pipe.silo_contribution(g, silo, scale, active, keys,
                                          state, bound), norm

    fn = jax.jit(fn)
    with _PIPE_CACHE_LOCK:
        _PIPE_CACHE.append((key, fn))
        if len(_PIPE_CACHE) > _PIPE_CACHE_MAX:
            del _PIPE_CACHE[0]
    return fn


# ---------------------------------------------------------------------------
# Sharded round accumulation (many-silo scale-out)


class _ShardedAccumulator:
    """Accumulate per-silo ``(P,)`` fp32 buffers across worker threads while
    staying BIT-IDENTICAL to the serial left fold.

    The parameter axis is split into ``workers`` contiguous shards; each
    worker owns ``acc[lo:hi]`` and folds the incoming buffers' matching
    slices strictly in arrival (= silo) order off its own FIFO queue. Per
    element the additions happen in exactly the serial order — slicing
    commutes with an elementwise sum — so the sharded total equals the
    serial ``((b0 + b1) + b2) + ...`` bitwise, while the fold itself runs
    ``workers``-wide (numpy's buffer add releases the GIL)."""

    def __init__(self, first: np.ndarray, workers: int):
        self._acc = np.array(first, np.float32, copy=True)
        n = self._acc.shape[0]
        workers = max(1, min(int(workers), n))
        bounds = np.linspace(0, n, workers + 1).astype(int)
        self._spans = [(int(lo), int(hi)) for lo, hi in
                       zip(bounds[:-1], bounds[1:]) if hi > lo]
        self._queues = [queue.Queue() for _ in self._spans]
        self._errors: list = []
        self._threads = []
        for (lo, hi), q in zip(self._spans, self._queues):
            t = threading.Thread(target=self._worker, args=(lo, hi, q),
                                 daemon=True)
            t.start()
            self._threads.append(t)

    def _worker(self, lo: int, hi: int, q: queue.Queue):
        acc = self._acc[lo:hi]
        while True:
            buf = q.get()
            if buf is None:
                return
            try:
                acc += buf[lo:hi]
            except Exception as e:  # surfaced by result()
                self._errors.append(e)

    def add(self, buf: np.ndarray) -> None:
        for q in self._queues:
            q.put(buf)

    def result(self) -> np.ndarray:
        for q in self._queues:
            q.put(None)
        for t in self._threads:
            t.join()
        if self._errors:
            raise self._errors[0]
        return self._acc


# ---------------------------------------------------------------------------
# Untrusted storage (everything at rest is encrypted)


class UntrustedStorage:
    def __init__(self):
        self.blobs: dict[str, bytes] = {}

    def put(self, asset_id: str, blob: bytes):
        self.blobs[asset_id] = blob

    def get(self, asset_id: str) -> bytes:
        try:
            return self.blobs[asset_id]
        except KeyError:
            raise KeyError(
                f"unknown asset {asset_id!r} in untrusted storage "
                f"({len(self.blobs)} assets held); was it ever uploaded, "
                f"or was it garbage-collected?") from None


# ---------------------------------------------------------------------------
# Components


@dataclass
class Component:
    name: str
    service: "ManagementService"
    report: object = None

    def __post_init__(self):
        # deployment snapshot: the ledger + wire configs in force when this
        # component was launched. The component measures *its own* launch
        # parameters — a component deployed against different enforcement
        # terms (or speaking a different wire codec / packed layout)
        # genuinely attests to a different value (the check is not
        # self-fulfilling against the verifier's expectation)
        self.launch_ledger_config = dict(self.service.ledger_config) \
            if self.service is not None else {}
        self.launch_wire_config = dict(self.service.wire_config) \
            if self.service is not None else {}

    def measurement(self) -> str:
        code = measure_modules(_guarded_modules())
        return _bind_configs(code, self.launch_ledger_config,
                             self.launch_wire_config)

    def attest(self, policy: LaunchPolicy):
        self.report = self.service.attestation.issue(
            self.name, self.measurement(), policy.hash(),
            nonce=self.name + "-n0")
        return self.report


@dataclass
class DataHandler(Component):
    """One per dataset owner: runs the model owner's (sandboxed) data-handling
    code on the silo's data; emits encrypted, clipped, DP-masked updates via
    the shared :class:`DPPipeline` engine (the same ``silo_contribution``
    stage the SPMD barrier tier psums)."""
    silo_idx: int = 0
    data: Optional[dict] = None
    sandbox: Sandbox = field(default_factory=Sandbox)
    channel: Optional[SecureChannel] = None
    # the (attested) admin this handler trusts for budget verdicts; when
    # set, caller-supplied verdicts are ignored — an untrusted driver can't
    # fabricate an all-allowed vector
    admin: Optional["Admin"] = None
    # wire codec: 'packed' ships raw flat buffers + XOR-delta param sync;
    # 'pickle' keeps the legacy pytree blobs (benchmark baseline)
    codec: str = "packed"
    # chaos-injection hook (core/tee/faults.py): called with the silo index
    # at compute_update entry — an injected crash raises SiloCrashError
    # there, an injected hang sleeps past the round deadline. None in
    # production: zero overhead. Deliberately NOT part of the guarded
    # measurement (the harness lives outside the trusted computing base).
    fault_hook: Optional[Callable] = None

    def __post_init__(self):
        super().__post_init__()
        # packed-params cache for the delta broadcast: the pinned packed
        # buffer, its layout, the layout fingerprint (also pinned through
        # the launch wire config when the session declared one) and the
        # epoch of the last applied broadcast
        self._cached_buf: Optional[np.ndarray] = None
        self._cached_layout = None
        self._params_epoch: int = -1
        pinned = self.launch_wire_config.get("layout")
        self._pinned_fp: Optional[bytes] = bytes.fromhex(pinned) \
            if pinned else None
        # digest of the last sealed update this handler emitted — the leaf
        # it reports to the admin for the round's Merkle batch tag
        self.last_leaf: Optional[bytes] = None
        # speculative wire rounds: a tiny key-tagged cache of this silo's
        # standard-normal streams. The admin's key schedule makes round
        # t+1's lambda-correction stream (prev_key) the SAME stream as round
        # t's xi (advance() sets prev_key = raw(key_xi)), so a handler that
        # kept its round-t xi skips one full P-length threefry/Box-Muller
        # draw per round — the dominant per-handler compute at large P. The
        # cache key is the raw 8-byte key value itself, so a resync, rejoin
        # or skipped round can never alias: wrong round => different key
        # bytes => miss => inline draw through the SAME jit (bit-identical
        # to the serial path by construction).
        self.speculative: bool = False
        self._stream_cache: dict = {}
        self._spec_hits: int = 0
        self._spec_pipe: Optional[DPPipeline] = None

    def _check_pin(self, fp: bytes) -> None:
        if self._pinned_fp is not None and fp != self._pinned_fp:
            raise wire.WireFormatError(
                f"{self.name}: broadcast layout fingerprint does not match "
                f"the attested session layout (possible model substitution)")

    def _sync_params(self, params_blob: bytes):
        """Decode a params broadcast. FULL messages (re)pin the packed
        cache; DELTA messages apply the XOR delta to the pinned buffer —
        bit-exact, zero float drift — and raise :class:`StaleParamsError`
        when this handler missed rounds (the admin then resyncs it with a
        full blob). Legacy pickle blobs pass straight through."""
        msg = wire.decode(params_blob)
        if msg.kind == wire.KIND_PICKLE:
            return wire.decode_tree(params_blob)
        if msg.kind == wire.KIND_FULL:
            layout, buf = wire.decode_full(msg)
            self._check_pin(msg.layout_fp)
            if self._pinned_fp is None:
                # pin the attested initial params' layout: later broadcasts
                # for a different model shape are rejected, not applied
                self._pinned_fp = msg.layout_fp
            self._cached_layout, self._cached_buf = layout, buf.copy()
            self._params_epoch = msg.epoch
            # numpy views into the cached buffer — no eager per-leaf jax
            # dispatch; the jitted grad fn device_puts them on call (the
            # leaf-count-many slice ops here used to dominate a handler's
            # round at many-silo scale)
            return wire.unpack_np(layout, self._cached_buf)
        if msg.kind == wire.KIND_DELTA:
            if self._cached_buf is None:
                raise wire.StaleParamsError(
                    f"{self.name}: delta broadcast but no pinned params "
                    f"(never synced) — need a full resync")
            if msg.epoch != self._params_epoch + 1:
                raise wire.StaleParamsError(
                    f"{self.name}: delta epoch {msg.epoch} does not follow "
                    f"cached epoch {self._params_epoch} (missed rounds) — "
                    f"need a full resync")
            self._check_pin(msg.layout_fp)
            self._cached_buf = wire.apply_delta(self._cached_layout,
                                                self._cached_buf, msg)
            self._params_epoch = msg.epoch
            return wire.unpack_np(self._cached_layout, self._cached_buf)
        raise wire.WireFormatError(
            f"{self.name}: unexpected wire kind {msg.kind} in params sync")

    def _remember_stream(self, tag: bytes, stream) -> None:
        """Insert with a hard cap of two entries (current xi + the round it
        came from): at any round the only reusable streams are xi(t) — this
        round's, becoming next round's xp — and a prefetched xi(t+1)."""
        cache = self._stream_cache
        cache[tag] = stream
        while len(cache) > 2:
            cache.pop(next(iter(cache)))

    def _round_streams(self, pipe: DPPipeline, keys: BarrierKeys,
                       state: NoiseState, use_prev: bool):
        """Draw (or recall) this round's xi / xp streams through the shared
        :meth:`DPPipeline.noise_stream` jit. Serial and speculative modes
        both call this — the ONLY difference is whether the cache is
        consulted, and a hit returns the very array the same jit produced
        earlier, so the two modes are bitwise indistinguishable."""
        xi_tag = np.asarray(keys.key_xi).tobytes()
        xi = self._stream_cache.get(xi_tag) if self.speculative else None
        if xi is None or xi.shape[0] != pipe.layout.total:
            xi = pipe.noise_stream(keys.key_xi, self.silo_idx)
        else:
            self._spec_hits += 1
        if self.speculative:
            self._remember_stream(xi_tag, xi)
        xp = None
        if use_prev:
            xp_tag = np.asarray(state.prev_key).tobytes()
            xp = self._stream_cache.get(xp_tag) if self.speculative else None
            if xp is None or xp.shape[0] != pipe.layout.total:
                xp = pipe.noise_stream(state.prev_key, self.silo_idx)
            else:
                self._spec_hits += 1
        return xi, xp

    def prefetch_round(self, keys: BarrierKeys) -> None:
        """Speculatively draw round-(t+1)'s xi stream while round t's
        aggregation/broadcast tail is still in flight (the driver calls this
        between submitting finish_round and collecting it). Safe against
        every failure mode by the cache-tag construction: a membership
        change does not invalidate xi (the stream is a function of key and
        silo only — participation gates ride in the scales), and any resync
        or reschedule that lands a different key simply misses the cache."""
        if not self.speculative or self._spec_pipe is None:
            return
        tag = np.asarray(keys.key_xi).tobytes()
        if tag not in self._stream_cache:
            self._remember_stream(
                tag, self._spec_pipe.noise_stream(keys.key_xi,
                                                  self.silo_idx))

    def _masked_contrib(self, pipe: DPPipeline, grads, active,
                        keys: BarrierKeys, state: NoiseState, clip_bound,
                        admin_row=None):
        """The handler's norm -> clip_scale -> silo_contribution stages as
        ONE jitted dispatch, shared by every handler of the session (the
        silo index is a traced argument — see ``_shared_pipe_fn``): the
        per-round protocol cost is the codec + channel crypto, not hundreds
        of eager op dispatches or n XLA compiles. The admin-mask
        construction keeps the eager path — its single-row reconstruction
        relies on a concrete participation set.

        On the packed pairwise path the xi/xp noise streams enter as
        ARGUMENTS (``_round_streams``) rather than being drawn in-graph, so
        the speculative scheduler can reuse round-t's xi as round-(t+1)'s
        xp without any cross-graph bitwise exposure."""
        if pipe.priv.mask_mode == "admin":
            norm = pipe.norm_tree(grads)
            scale = pipe.clip_scale(norm, clip_bound)
            return pipe.silo_contribution(grads, self.silo_idx, scale,
                                          active, keys, state, clip_bound,
                                          admin_row=admin_row), norm
        has_prev = state.prev_active is not None
        if pipe.priv.mask_mode != "pairwise":
            fn = _shared_pipe_fn(pipe, has_prev)
            return fn(grads, jnp.asarray(self.silo_idx, jnp.int32), active,
                      keys, state, jnp.asarray(clip_bound, jnp.float32))
        use_prev = pipe.priv.noise_lambda > 0.0
        xi, xp = self._round_streams(pipe, keys, state, use_prev)
        fn = _shared_pipe_fn(pipe, has_prev, "xi+xp" if use_prev else "xi")
        args = (grads, jnp.asarray(self.silo_idx, jnp.int32), active, keys,
                state, jnp.asarray(clip_bound, jnp.float32), xi)
        return fn(*args, xp) if use_prev else fn(*args)

    def compute_update(self, params_blob: bytes, grad_fn: Callable,
                       priv: PrivacyConfig, keys: BarrierKeys, n_silos: int,
                       clip_bound: float, active=None,
                       noise_state: Optional[NoiseState] = None,
                       verdicts=None, admin_row=None) -> bytes:
        """``active``: this round's participation set distributed by the
        admin alongside the step keys — the zero-sum ring and this silo's
        noise share are built over the actual contributors. ``noise_state``
        carries the admin's step-(t-1) key for the lambda correction.
        ``verdicts``: the per-silo budget verdict vector. With a wired
        ``admin`` (the normal session setup) the handler asks that attested
        component for its OWN verdict and ignores the caller's value, so an
        untrusted training driver can neither omit nor fabricate it —
        enforcement sits inside the TEE boundary. ``admin_row``: admin-mode
        O(P) fan-out — the ``(closing, row_tree)`` pair the admin
        distributed; only the closing silo consumes it."""
        if self.fault_hook is not None:
            self.fault_hook(self.silo_idx)
        if self.admin is not None:
            allowed = self.admin.verdict_for(self.silo_idx)
        else:
            allowed = verdicts is None or \
                bool(np.asarray(verdicts)[self.silo_idx])
        if not allowed:
            raise PermissionError(
                f"silo {self.silo_idx}: owner's privacy budget is exhausted "
                f"(ledger verdict); refusing to compute an update")
        params = self._sync_params(params_blob)
        # untrusted model-owner code inside the sandbox (R1/R2)
        loss, grads = self.sandbox.run(grad_fn, params, self.data)
        pipe = DPPipeline(priv, flatbuf.layout_of(grads), n_silos)
        if priv.mask_mode == "pairwise":
            # remembered for prefetch_round: next round's stream needs this
            # round's layout/engine config (which the driver doesn't hold)
            self._spec_pipe = pipe
        active = pipe.full_active() if active is None \
            else jnp.asarray(active, jnp.bool_)
        state = noise_state if noise_state is not None \
            else init_state(jnp.zeros((2,), jnp.uint32), n_silos=n_silos)
        row = admin_row[1] if admin_row is not None \
            and self.silo_idx == admin_row[0] else None
        contrib, norm = self._masked_contrib(pipe, grads, active, keys,
                                             state, clip_bound,
                                             admin_row=row)
        if self.codec == "packed":
            # ship the packed (P,) buffer straight off the DP engine — one
            # contiguous memoryview into the channel, no tree re-traversal
            if isinstance(contrib, jax.Array) and contrib.ndim == 1:
                buf = np.asarray(contrib)
            else:  # admin/none constructions yield trees
                buf = wire.pack_np(pipe.layout, pipe.finalize(contrib))
            payload = wire.encode_update(pipe.layout, buf, float(loss),
                                         float(norm))
        else:
            payload = _ser({"update": pipe.finalize(contrib),
                            "loss": jnp.asarray(loss), "norm": norm},
                           codec="pickle")
        blob = self.channel.send(payload)
        # the leaf this handler reports to the admin for the round's Merkle
        # batch tag: a digest of the ENTIRE channel blob (counter prefix
        # included), so a substituted, truncated or cross-round-replayed
        # blob cannot sit under the round's root
        self.last_leaf = hashlib.sha256(blob).digest()
        return blob


@dataclass
class ModelUpdater(Component):
    """Single component for the model owner: aggregates masked updates and
    applies the (sandboxed) model-updating code. Never sees raw gradients;
    the aggregate is divided by the silos that actually contributed.

    Many-silo scale-out (ISSUE 7): per-message authentication runs through
    the round's Merkle batch tag when the admin provides one (one keyed HMAC
    per round + an O(log n) path per message instead of n full HMAC passes —
    see core/tee/merkle.py), accumulation can shard over worker threads
    (``shard_workers``; bit-identical to the serial fold), and out-of-order
    arrivals are staged and flushed in the round's expected silo order so
    the sum's fp association never depends on scheduling."""
    channels: dict = field(default_factory=dict)
    received_updates: list = field(default_factory=list)
    # admin<->updater aggregation key for batch tags (KDS-released against
    # both components' attestation measurements)
    agg_key: Optional[bytes] = None
    # parameter-axis accumulation threads; 0/1 = serial left fold
    shard_workers: int = 0
    # audit-trail bound: received_updates keeps the newest entries only (at
    # 400 silos an unbounded trail pins n*P floats per round forever).
    # Sessions size it from n_silos (api.from_silos: max(256, 2n)); every
    # entry aged out is counted in truncated_entries so a shortened trail
    # is visible to auditors instead of silently deleted.
    received_cap: int = 256
    truncated_entries: int = 0
    # chaos-injection hook (core/tee/faults.py): called at finish_round
    # entry — i.e. between the last ingest and the round commit, the
    # crash window the RoundJournal recovery path covers. None in
    # production: zero overhead.
    fault_hook: Optional[Callable] = None

    def verify_batch_tag(self, batch: dict) -> None:
        """Check the round-level MAC binding (round, leaf count, Merkle
        root) under the admin<->updater aggregation key."""
        if self.agg_key is None:
            raise wire.WireFormatError(
                "updater holds no aggregation key: cannot verify a Merkle "
                "batch tag (was the updater attested and keyed via the KDS?)")
        mac = hmac.new(self.agg_key,
                       b"batch-mac-v1"
                       + struct.pack("<QI", batch["round"],
                                     len(batch["names"]))
                       + batch["root"], hashlib.sha256).digest()
        if not hmac.compare_digest(mac, batch["mac"]):
            raise wire.WireFormatError(
                "batch tag MAC verification failed (forged or tampered "
                "batch tag); refusing the round")

    def begin_round(self, params, expected=None, batch=None,
                    batch_mode: bool = False) -> dict:
        """Open a streaming aggregation round: updates are ingested one at a
        time as handlers produce them, so decrypt+accumulate of silo i
        overlaps silo i+1's compute.

        ``expected``: the round's handler names in silo order. Arrivals are
        staged and flushed in exactly this order (the sum's fp association
        is part of the cross-tier bit-parity contract), so out-of-order
        ingestion is safe; a round closing with members missing fails.
        Without it, arrival order is trusted (the legacy single-caller path).

        ``batch``: the admin's Merkle batch tag — verified now, each
        message's leaf checked against its O(log n) path at ingest, and the
        per-message channel HMAC skipped. ``batch_mode=True`` without a tag
        defers verification to :meth:`finish_round` (the pipelined runner
        streams updates before the admin has seen every leaf); leaves are
        recorded per message and the aggregate only commits after the late
        tag verifies every one of them."""
        if batch is not None:
            self.verify_batch_tag(batch)
            if expected is None:
                expected = list(batch["names"])
            elif list(expected) != list(batch["names"]):
                raise wire.WireFormatError(
                    "round's expected silo order disagrees with the batch "
                    "tag's leaf order")
            batch_mode = True
        expected = list(expected) if expected is not None else None
        return {"layout": flatbuf.layout_of(params), "params": params,
                "total": None, "acc": None, "losses": [],
                "expected": expected,
                "expected_set": set(expected) if expected is not None
                else None,
                "next": 0, "pending": {}, "seen": set(),
                "batch": batch, "batch_mode": batch_mode, "leaves": []}

    def _accumulate(self, rs: dict, buf: np.ndarray, loss: float) -> None:
        """One buffer into the round total, in flush order. The first buffer
        seeds either the serial fold or the sharded accumulator — both
        reproduce the serial left fold bitwise (see _ShardedAccumulator)."""
        rs["losses"].append(loss)
        if rs["acc"] is not None:
            rs["acc"].add(buf)
        elif rs["total"] is None:
            if self.shard_workers > 1:
                rs["acc"] = _ShardedAccumulator(buf, self.shard_workers)
            else:
                rs["total"] = buf
        else:
            rs["total"] = rs["total"] + buf

    def ingest(self, round_state: dict, silo: str, blob: bytes) -> None:
        """Authenticate + decrypt + decode + accumulate one handler's sealed
        update. Packed KIND_UPDATE messages accumulate directly on the flat
        ``(P,)`` buffers (``np.frombuffer`` views — zero deserialization);
        legacy pickle payloads are packed into the same buffers first. Both
        give bit-identical aggregates (packing is a permutation with zero
        padding; slicing commutes with the silo-ordered sum).

        A duplicate silo in one round is rejected before any crypto runs;
        with a batch tag, a message whose digest is not under the round's
        Merkle root is rejected here — detected AND attributed."""
        rs = round_state
        if silo in rs["seen"]:
            raise wire.WireFormatError(
                f"{silo}: duplicate update in one round (rejected)")
        if rs["expected_set"] is not None and silo not in rs["expected_set"]:
            raise wire.WireFormatError(
                f"{silo}: update from a silo outside this round's "
                f"expected set (rejected)")
        rs["seen"].add(silo)
        batch = rs["batch"]
        if batch is not None:
            leaf = hashlib.sha256(blob).digest()
            path = batch["paths"].get(silo)
            if path is None or not merkle.verify_path(batch["root"], leaf,
                                                      path):
                raise wire.WireFormatError(
                    f"{silo}: sealed update does not match the round's "
                    f"Merkle batch tag (tampered or substituted in "
                    f"transit); update rejected")
            raw = self.channels[silo].recv(blob, verify=False)
        elif rs["batch_mode"]:
            # tag arrives at finish_round: record the leaf now, decrypt
            # optimistically, commit nothing until every leaf verifies
            rs["leaves"].append((silo, hashlib.sha256(blob).digest()))
            raw = self.channels[silo].recv(blob, verify=False)
        else:
            raw = self.channels[silo].recv(blob)
        layout = rs["layout"]
        msg = wire.decode(raw)
        if msg.kind == wire.KIND_UPDATE:
            buf, loss, _norm = wire.decode_update(msg, layout)
            self.received_updates.append(jax.tree.map(
                np.asarray, wire.unpack_np(layout, buf, dtype=np.float32)))
        else:
            payload = wire.decode_tree(raw)
            self.received_updates.append(
                jax.tree.map(np.asarray, payload["update"]))
            loss = float(payload["loss"])
            buf = wire.pack_np(layout, payload["update"])
        overflow = len(self.received_updates) - self.received_cap
        if overflow > 0:
            self.truncated_entries += overflow
            del self.received_updates[:-self.received_cap]
        # both sides are fp32 by wire contract (decode_update / pack_np):
        # a plain add keeps the ingestion path copy-free
        if rs["expected"] is None:
            self._accumulate(rs, buf, loss)
            return
        rs["pending"][silo] = (buf, loss)
        exp, nxt = rs["expected"], rs["next"]
        while nxt < len(exp) and exp[nxt] in rs["pending"]:
            b, l = rs["pending"].pop(exp[nxt])
            self._accumulate(rs, b, l)
            nxt += 1
        rs["next"] = nxt

    def finish_round(self, round_state: dict, update_fn: Callable,
                     lr: float, batch: Optional[dict] = None):
        """Close the round: verify a deferred batch tag (every recorded leaf
        must sit under the MACed root — failures are attributed by silo and
        the aggregate is DISCARDED, not committed), check the expected set
        is complete, divide by the actual contribution count and run the
        (sandbox-supplied) model-updating code."""
        if self.fault_hook is not None:
            self.fault_hook()
        rs = round_state
        if rs["batch_mode"] and rs["batch"] is None:
            if batch is None:
                raise wire.WireFormatError(
                    "round opened in batch-MAC mode but closed without a "
                    "batch tag; aggregate discarded")
            self.verify_batch_tag(batch)
            bad = []
            for silo, leaf in rs["leaves"]:
                path = batch["paths"].get(silo)
                if path is None or not merkle.verify_path(batch["root"],
                                                          leaf, path):
                    bad.append(silo)
            if bad:
                raise wire.WireFormatError(
                    f"batch tag verification failed for {', '.join(bad)}: "
                    f"sealed update(s) do not match the round's Merkle "
                    f"root (tampered or substituted); aggregate discarded")
        if rs["expected"] is not None and rs["next"] != len(rs["expected"]):
            missing = [s for s in rs["expected"][rs["next"]:]
                       if s not in rs["pending"]]
            raise wire.WireFormatError(
                f"round closed with updates missing from "
                f"{', '.join(missing)}; aggregate discarded")
        total = rs["acc"].result() if rs["acc"] is not None else rs["total"]
        n_contrib = max(len(rs["losses"]), 1)
        mean_update = wire.unpack_np(
            rs["layout"], total / np.float32(n_contrib), dtype=np.float32)
        new_params = update_fn(rs["params"], mean_update, lr)
        return new_params, float(np.mean(rs["losses"]))

    def aggregate(self, blobs: dict, params, update_fn: Callable, lr: float,
                  n_silos: Optional[int] = None,
                  batch: Optional[dict] = None):
        """``n_silos`` is accepted for call-site compatibility but the
        divisor is the actual contribution count (len(blobs)) — dropped
        silos shrink the mean, matching the SPMD tiers. ``batch``: the
        round's Merkle batch tag (per-ingest path verification)."""
        rs = self.begin_round(params, expected=list(blobs), batch=batch)
        for silo, blob in blobs.items():
            self.ingest(rs, silo, blob)
        return self.finish_round(rs, update_fn, lr)


@dataclass
class Admin(Component):
    """Coordinates iterations, owns the per-step mask/noise keys (32 bytes
    per step — the whole of the 'mask distribution' on the pairwise path),
    the session's privacy ledger (per-silo spend, budgets and verdicts) and
    the noise-correction state."""
    root_key: Optional[jax.Array] = None
    ledger: Optional[PrivacyLedger] = None
    n_silos: int = 0
    noise_state: Optional[NoiseState] = None
    # admin<->updater aggregation key for Merkle batch tags (KDS-released)
    agg_key: Optional[bytes] = None
    _verdict_cache: Optional[tuple] = field(default=None, repr=False)

    # legacy spelling: the ledger *is* the session accountant
    @property
    def accountant(self) -> Optional[PrivacyLedger]:
        return self.ledger

    @accountant.setter
    def accountant(self, value) -> None:
        self.ledger = value

    def keys_for_step(self, step: int) -> BarrierKeys:
        return step_keys(self.root_key, jnp.asarray(step))

    def verdicts(self) -> np.ndarray:
        """Per-silo budget verdicts the admin distributes with the step keys
        (True = the owner still has budget). All-allowed without a ledger.

        The vector is cached per ledger state (steps, session budget, the
        per-silo budget table): verdicts only move when the ledger records a
        round or an operator edits budgets, so n handlers asking in one
        round cost one ledger sweep, not n — O(n) per round instead of
        O(n^2) at 400 silos."""
        if self.ledger is None:
            return np.ones(max(self.n_silos, 1), bool)
        fp = (self.ledger.steps, self.ledger.epsilon_budget,
              tuple(sorted(self.ledger.budgets.items())))
        if self._verdict_cache is None or self._verdict_cache[0] != fp:
            self._verdict_cache = (fp, self.ledger.allowed_mask())
        return self._verdict_cache[1]

    def verdict_for(self, silo: int) -> bool:
        """One silo's budget verdict, O(1) against the cached vector."""
        return bool(np.asarray(self.verdicts())[silo])

    def batch_tag(self, leaves: list, round_id: int) -> dict:
        """Build the round's Merkle batch tag over ``[(name, leaf), ...]``
        in silo order (see core/tee/merkle.py): one tree over the sealed-
        blob digests, one keyed HMAC binding (round, leaf count, root) under
        the admin<->updater aggregation key, and each silo's O(log n)
        authentication path keyed by handler name."""
        if self.agg_key is None:
            raise ValueError(
                "admin holds no aggregation key: cannot issue a Merkle "
                "batch tag (was the admin attested and keyed via the KDS?)")
        names = [name for name, _ in leaves]
        tree = merkle.MerkleTree([leaf for _, leaf in leaves])
        mac = hmac.new(self.agg_key,
                       b"batch-mac-v1"
                       + struct.pack("<QI", round_id, len(names))
                       + tree.root, hashlib.sha256).digest()
        return {"round": int(round_id), "names": names, "root": tree.root,
                "mac": mac,
                "paths": {name: tree.path(i)
                          for i, name in enumerate(names)}}

    def closing_mask_row(self, priv: PrivacyConfig, template, keys,
                         active, state, clip_bound):
        """The admin-mode closing row, computed ONCE per round on the admin
        and distributed to the one closing handler — O(P) admin fan-out
        instead of every handler regenerating all n mask rows (the (n, P)
        stack) to reconstruct it. Returns ``(closing_index, row)``."""
        pipe = DPPipeline(priv, flatbuf.layout_of(template), self.n_silos)
        return pipe.admin_closing_row(template, active, keys, state,
                                      clip_bound)

    def state_for_step(self) -> NoiseState:
        """The correction state handlers need this round (prev step's 32-byte
        noise key + the participation set it was drawn over)."""
        if self.noise_state is None:
            self.noise_state = init_state(jnp.zeros((2,), jnp.uint32),
                                          n_silos=max(self.n_silos, 1))
        return self.noise_state

    def advance(self, keys: BarrierKeys, active) -> None:
        """End-of-round bookkeeping: roll the correction state forward and
        record the round's participation bitmask with the ledger (the write
        that attributes this round's privacy loss to exactly the silos that
        contributed, and may flip budget verdicts for the next round)."""
        from repro.core.masking import _raw
        active = jnp.asarray(active, jnp.bool_)
        self.noise_state = NoiseState(prev_key=_raw(keys.key_xi),
                                      has_prev=jnp.ones((), jnp.bool_),
                                      prev_active=active)
        if self.ledger is not None:
            self.ledger.record(np.asarray(active))

    def sign_spend_report(self, round_trip_s: Optional[dict] = None) -> dict:
        """The ledger's spend report, HMAC-signed with a key derived from
        this admin's attestation identity — the hardware-root signature over
        its measured report, which is NOT embedded in the output: a verifier
        must recompute it through the attestation service (the root of
        trust), so a driver holding only the JSON can neither verify nor
        re-sign a tampered body. Verify with
        :func:`repro.analysis.report.verify_spend_report(report,
        attestation_service)` (ROADMAP: ledger-signed spend reports).

        ``round_trip_s``: per-silo round-trip EMAs (SiloTelemetry.snapshot)
        folded into the per-silo rows BEFORE signing, so the operator's
        latency view carries the same integrity as the spend columns."""
        if self.ledger is None:
            raise ValueError("admin has no ledger to report on")
        report = self.ledger.spend_report(round_trip_s=round_trip_s)
        if self.report is None:
            return report  # unattested admin: plain report, nothing to bind
        signed = dict(report)
        signed["signature"] = {
            "scheme": "hmac-sha256/attestation-identity",
            "hmac": spend_report_mac(report, self.report.signature),
            # identity claim only — the signature over it stays with the
            # attestation service, where the verifier recomputes it
            "signer": {
                "component": self.report.component,
                "code_measurement": self.report.code_measurement,
                "policy_hash": self.report.policy_hash,
                "nonce": self.report.nonce,
            },
        }
        return signed


class ManagementService:
    """Sets up a training session and tracks metadata (paper §3.2)."""

    def __init__(self):
        self.attestation = AttestationService()
        self.kds = KeyDistributionService(self.attestation)
        self.storage = UntrustedStorage()
        self.policy = LaunchPolicy()
        self.sessions: dict[str, dict] = {}
        self.ledger_config: dict = {}
        # the wire codec is part of the trusted protocol surface: sessions
        # may pin the packed-layout fingerprint of the model they agreed to
        # train, binding the wire format into every component's measurement
        self.wire_config: dict = {"codec": wire.WIRE_CODEC_ID}

    def expected_measurement(self) -> str:
        """Guarded code measurement, extended with the session's ledger
        config (per-silo budgets are part of what the owners agreed to) and
        wire config (codec id + optionally the pinned packed-layout
        fingerprint): a service launched with different enforcement or
        protocol parameters measures differently and the KDS withholds
        keys."""
        return _bind_configs(measure_modules(_guarded_modules()),
                             self.ledger_config, self.wire_config)

    def create_session(self, session_id: str, n_silos: int,
                       priv: PrivacyConfig,
                       ledger_config: Optional[dict] = None,
                       wire_config: Optional[dict] = None) -> dict:
        if ledger_config is not None:
            cfg = ledger_config
        else:
            # default must be structurally identical to what a real
            # ledger's config_dict() yields for these terms, or two
            # semantically-equal sessions would measure differently
            cfg = PrivacyLedger.from_privacy_config(priv, n_silos).config_dict()
        wcfg = dict(self.wire_config) if wire_config is None \
            else dict(wire_config)
        if self.sessions and (cfg != self.ledger_config
                              or wcfg != self.wire_config):
            # the measurement gating *all* keys on this service binds one
            # ledger + wire config; silently swapping either would deny
            # earlier sessions' components their keys. One service instance
            # = one config — deploy another service for another.
            raise ValueError(
                "this ManagementService already measures a different ledger/"
                "wire config; deploy a separate service for a session with "
                "different enforcement or protocol terms")
        self.ledger_config = cfg
        self.wire_config = wcfg
        s = {"id": session_id, "n_silos": n_silos, "priv": priv,
             "progress": 0, "components": {},
             "ledger_config": dict(cfg), "wire_config": dict(wcfg)}
        self.sessions[session_id] = s
        return s
