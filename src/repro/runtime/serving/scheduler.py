"""Continuous-batching scheduler over the slot-recycled paged KV cache.

Contrast with ``runtime.server.WaveServer`` (the measured baseline): instead
of draining a whole same-length wave before touching the queue, the
scheduler revisits admission at *every* step — a finished request's slot is
released immediately, its pages go back to the free list, and the next
queued request is admitted into the recycled slot with its pages zeroed
in-kernel (``PagePool.alloc``). Prefill is chunked into the decode loop: an
admitted request advances one ``prefill_chunk`` of its prompt per step while
other slots keep decoding, so a long prompt never stalls the batch.

Admission is weighted deficit-round-robin over per-tenant subqueues:
each tenant accrues credit in proportion to its weight while it waits and
spends one credit per admitted request, which converges to weighted shares
under backlog while staying strictly FIFO *within* each tenant. Subqueues
also make admission O(free slots x tenants) instead of the old O(queue^2)
scan-and-remove, and the scan stops outright once every backlogged tenant
is at its slot cap.

Two opt-in throughput layers ride on the same pool, both leak-free by
construction:

* **Prefix sharing** (``prefix_sharing=True``): an admitted request whose
  prompt starts with full pages already cached *for its own tenant* maps
  those pages read-only (refcounted, never zeroed, never written — the COW
  boundary is where its fresh pages begin) and starts prefill at the shared
  boundary. Cross-tenant sharing is structurally impossible: the tenant id
  is part of the prefix-index key (see ``paged_cache``).
* **Speculative decoding** (``speculative=True``): a draft model — the
  first ``draft_layers`` layers of the target, sharing its embedding and
  head — proposes ``spec_k - 1`` tokens per tick from a parallel draft
  pool (same page ids, same tables), and the target verifies all of them
  in ONE chunk-shaped ``paged_step`` call (``logits_mode="all"`` — the same
  function and kernels as prefill, with a full-chunk readout). Greedy
  accept keeps the emitted stream token-identical to the non-speculative
  scheduler (a test invariant, like the wave parity); the rejected tail is
  erased in-kernel (``PagePool.rollback``) from both pools before the next
  tick. The compiled-graph budget stays flat: the draft brings its own
  decode/chunk pair, verification is one extra readout variant of the
  existing chunk graph, and the target's plain decode graph is retired.

Shapes never depend on which requests are in flight — per-request variation
lives entirely in the block tables, lengths and validity masks, which are
data. Page allocations are bucketed to powers of two so recycled claims fit
each other's freed runs.

Every tick is traced with ``jax.profiler.TraceAnnotation`` spans, inert
unless a profiler is running: ``serve.tick`` around ``step``; inside it
``serve.admit``, ``serve.decode`` and ``serve.prefill``; inside the last two
``serve.prepare`` (host token and validity arrays), ``serve.dispatch`` (the
jitted call, argument transfer included), ``serve.fetch`` (logits to the
host, waiting for the device) and ``serve.sample`` (argmax, append, finish).
A ``serve.dispatch`` span carries its call's increments of the
``ServerStats`` row and page counters as arguments, ``serve.admit`` its
increment of ``state_resets``, and a
``serve.first_token`` mark carries each request's admission wait and
prefill time (ms).

Token-for-token equivalence with the wave baseline (greedy argmax over the
same model) is a test invariant, not an aspiration: ``tests/test_serving.py``
asserts it under randomized admission/finish orders, and asserts the
speculative scheduler emits the identical stream.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
import warnings
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels.paged_attention.paged_attention import block_pages
from repro.runtime.server import Request, ServerStats
from repro.runtime.serving.paged_cache import PagePool


@dataclass
class _Slot:
    req: Request
    pos: int = 0                    # prompt tokens prefilled so far
    pending: Optional[int] = None   # next decode input (set at prefill end)
    seq: int = 0                    # original submit order (for re-queue)
    # effective prompt: the submitted prompt plus any tokens generated
    # before a preemption — restoring an evicted request is just prefilling
    # this (greedy decode makes the recompute token-identical, and the
    # chunked prefill's final argmax IS the next token, so nothing is
    # double-counted)
    prompt: Optional[np.ndarray] = None


def _bucket_pages(tokens_needed: int, page_size: int, cap: int) -> int:
    """Pages for ``tokens_needed``, rounded up to a power of two (so freed
    allocations are exchangeable between differently-sized requests)."""
    need = -(-tokens_needed // page_size)
    b = 1
    while b < need:
        b *= 2
    return min(b, cap)


def _draft_of(model, params, draft_layers: Optional[int]):
    """Build the draft (model, params) pair: the first ``draft_layers``
    layers of the target with the target's own embedding/final-norm/head
    (an early-exit draft — no second set of weights to train or ship).
    ``draft_layers=None`` or ``== n_layers`` is the self-draft degenerate
    case: the draft IS the target, acceptance is ~1, and the win comes
    purely from amortizing per-tick scheduler overhead over k tokens."""
    from repro.models.registry import build_model
    cfg = model.cfg
    Ld = cfg.n_layers if draft_layers is None else int(draft_layers)
    if not 1 <= Ld <= cfg.n_layers:
        raise ValueError(f"draft_layers={draft_layers} out of range for a "
                         f"{cfg.n_layers}-layer target")
    draft_model = build_model(dataclasses.replace(cfg, n_layers=Ld),
                              compute_dtype=model.compute_dtype)
    if Ld == cfg.n_layers:
        return draft_model, params
    draft_params = dict(params)
    draft_params["layers"] = jax.tree_util.tree_map(
        lambda x: x[:Ld], params["layers"])
    return draft_model, draft_params


class ContinuousServer:
    """Same submit/run surface as ``WaveServer``; continuous batching over
    a paged, slot-recycled KV cache, with optional same-tenant prefix
    sharing and speculative decoding."""

    def __init__(self, model, params, *, max_batch: int = 8,
                 max_len: int = 512, page_size: int = 16,
                 prefill_chunk: int = 16, n_pages: Optional[int] = None,
                 trace_logits: bool = False,
                 max_slots_per_tenant: Optional[int] = None,
                 tenant_weights: Optional[dict] = None,
                 prefix_sharing: bool = False,
                 speculative: bool = False, spec_k: int = 4,
                 draft_layers: Optional[int] = None):
        self.model = model
        self.params = params
        self.n_slots = max_batch
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        if model.reset_slot_state is not None and (prefix_sharing
                                                   or speculative):
            raise ValueError(
                f"{model.cfg.name} ({model.cfg.family}) keeps a recurrent "
                f"state per sequence: prefix sharing and speculative "
                f"rollback need snapshots of it, which the pool does not "
                f"take; serve it with prefix_sharing=False, "
                f"speculative=False")
        self.prefix_sharing = prefix_sharing
        self.speculative = speculative
        self.spec_k = spec_k
        draft_model = self.draft_params = None
        if speculative:
            if spec_k < 2:
                raise ValueError("spec_k must be >= 2 (k=1 is plain decode)")
            draft_model, self.draft_params = _draft_of(model, params,
                                                       draft_layers)
        per_slot = -(-max_len // page_size)
        self.pool = PagePool(model, n_slots=max_batch,
                             n_pages=n_pages or max_batch * per_slot,
                             page_size=page_size, pages_per_slot=per_slot,
                             draft_model=draft_model,
                             prefix_index=prefix_sharing)
        self.slots: list[Optional[_Slot]] = [None] * max_batch
        # per-tenant admission cap: one tenant's burst cannot monopolize the
        # batch (and with it the page pool) — the confidential-serving
        # analogue of the training tier's per-silo budget isolation.
        # Requests with tenant=None are exempt (single-operator use)
        self.max_slots_per_tenant = max_slots_per_tenant
        # per-tenant FIFO subqueues of (submit seq, request) + DRR credit
        self.tenant_weights = dict(tenant_weights or {})
        self.queues: dict[Optional[str], collections.deque] = {}
        self._deficit: dict[Optional[str], float] = {}
        self._seq = 0
        self.queued = 0
        self.stats = ServerStats()
        self.clock = 0  # scheduler steps; the latency currency
        # rid -> [logits row per generated token]; the leak-freedom probe
        # asserts these are BIT-equal between a recycled-slot run and a
        # fresh-cache run
        self.trace_logits = trace_logits
        self.logit_trace: dict[int, list[np.ndarray]] = {}
        self._step_fn = jax.jit(model.paged_step, donate_argnums=(2,))
        if speculative:
            self._draft_fn = jax.jit(draft_model.paged_step,
                                     donate_argnums=(2,))
            self._verify_fn = jax.jit(
                functools.partial(model.paged_step, logits_mode="all"),
                donate_argnums=(2,))

            def _propose(dp, pool, tables, base, t0, keff):
                """All spec_k - 1 draft proposals in ONE device call: a scan
                of the draft's decode step, greedy argmax feeding the next
                step on-device. k - 1 separate dispatches would pay the
                host-sync tax speculation exists to amortize."""
                import jax.numpy as jnp

                def body(carry, j):
                    pool, cur = carry
                    nv = jnp.where(j < keff - 1, 1, 0).astype(jnp.int32)
                    logits, pool = draft_model.paged_step(
                        dp, cur[:, None], pool, tables, base + j, nv)
                    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                    return (pool, nxt), nxt

                (pool, _), props = jax.lax.scan(
                    body, (pool, t0), jnp.arange(spec_k - 1))
                return jnp.transpose(props), pool

            self._propose_fn = jax.jit(_propose, donate_argnums=(1,))

    # ------------------------------------------------------------------ queue
    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.rid} exceeds max_len {self.max_len}")
        req.submit_step = self.clock
        req.t_submit = time.perf_counter()
        self.queues.setdefault(req.tenant, collections.deque()).append(
            (self._seq, req))
        self._seq += 1
        self.queued += 1

    # ------------------------------------------------------------- lifecycle
    def _tenant_slots(self, tenant: str) -> int:
        return sum(1 for s in self.slots
                   if s is not None and s.req.tenant == tenant)

    def _tenant_ok(self, tenant: Optional[str]) -> bool:
        return (self.max_slots_per_tenant is None or tenant is None
                or self._tenant_slots(tenant) < self.max_slots_per_tenant)

    def _weight(self, tenant: Optional[str]) -> float:
        return float(self.tenant_weights.get(tenant, 1.0))

    def _admit(self) -> None:
        """Weighted deficit-round-robin over per-tenant subqueues, one pick
        per free slot. Each successful admission pays the picked tenant one
        credit and accrues weight-proportional credit to every tenant still
        waiting, so long-run admissions converge to the weight ratios while
        a capped tenant can neither head-of-line-block others (its subqueue
        is simply ineligible) nor bank unbounded credit (accrual is
        normalized: one credit total is minted per admission)."""
        for i in range(self.n_slots):
            if self.queued == 0:
                return
            if self.slots[i] is not None:
                continue
            eligible = [t for t, q in self.queues.items()
                        if q and self._tenant_ok(t)]
            if not eligible:
                return  # every backlogged tenant capped: stop scanning
            t_star = max(eligible, key=lambda t: (self._deficit.get(t, 0.0),
                                                  -self.queues[t][0][0]))
            # pop the candidate BEFORE the allocation attempt: preemption
            # may re-queue a same-tenant victim at the front of this very
            # subqueue, so a popleft afterwards could remove the wrong entry
            seq, req = self.queues[t_star].popleft()
            self.queued -= 1
            # effective prompt: original prompt + tokens generated before a
            # preemption (empty for a first admission)
            eff = req.prompt if not req.generated else np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(req.generated, np.int32)])
            shared = (self.pool.prefix_lookup(req.tenant, eff)
                      if self.prefix_sharing else [])
            need = _bucket_pages(len(req.prompt) + req.max_new_tokens,
                                 self.pool.page_size,
                                 self.pool.tables.shape[1])
            if not self.pool.alloc(i, need, shared=shared):
                # pool pressure: preempt strictly-lower-priority slots
                # until the allocation fits, else put the candidate back
                # and retry next step (FIFO order kept either way)
                if not self._preempt_for(req, i, need, shared):
                    self.queues[t_star].appendleft((seq, req))
                    self.queued += 1
                    return
            backlogged = [t for t, q in self.queues.items() if q]
            if backlogged:
                W = sum(self._weight(t) for t in backlogged)
                for t in backlogged:
                    self._deficit[t] = (self._deficit.get(t, 0.0)
                                        + self._weight(t) / W)
            self._deficit[t_star] = self._deficit.get(t_star, 0.0) - 1.0
            self.stats.state_resets += self.pool.has_state
            if not self.queues[t_star]:
                del self.queues[t_star]
                self._deficit.pop(t_star, None)
            S0 = len(shared) * self.pool.page_size
            self.stats.shared_prompt_tokens += S0
            if req.t_admit is None:
                req.t_admit = time.perf_counter()
            self.slots[i] = _Slot(req, pos=S0, seq=seq,
                                  prompt=np.asarray(eff, np.int32))

    def _preempt_for(self, req: Request, i: int, need: int,
                     shared: list) -> bool:
        """Evict running slots whose priority is STRICTLY below ``req``'s
        (so equal-priority traffic can never preempt itself and there are no
        preemption cycles), cheapest recompute first (fewest generated
        tokens), until the allocation for slot ``i`` fits. Evicted requests
        go back to the FRONT of their tenant's subqueue under their original
        submit seq, so DRR ordering is undisturbed and they restore by
        recompute of prompt + generated — token-identical under greedy
        decode. Returns False (nothing evicted beyond what helped) when no
        strictly-lower-priority victim remains and the allocation still
        doesn't fit."""
        while True:
            victims = [j for j, s in enumerate(self.slots)
                       if s is not None and s.req.priority < req.priority]
            if not victims:
                return False
            j = min(victims, key=lambda j: (self.slots[j].req.priority,
                                            len(self.slots[j].req.generated)))
            s = self.slots[j]
            self.pool.release(j)
            self.slots[j] = None
            self.queues.setdefault(s.req.tenant,
                                   collections.deque()).appendleft(
                (s.seq, s.req))
            self.queued += 1
            self.stats.preemptions += 1
            if self.pool.alloc(i, need, shared=shared):
                return True

    def _finish(self, i: int, req: Request) -> None:
        req.done = True
        req.finish_step = self.clock
        self.stats.latencies.append(req.finish_step - req.submit_step)
        self.pool.release(i)
        self.slots[i] = None

    def _append(self, i: int, tok: int) -> bool:
        """Record a generated token; True when the request just finished."""
        req = self.slots[i].req
        req.generated.append(tok)
        self.stats.useful_tokens += 1
        if req.t_first_token is None:
            req.t_first_token = t = time.perf_counter()
            # a mark in the profile: its arguments carry the stamps
            with TraceAnnotation("serve.first_token",
                                 wait_ms=1e3 * (req.t_admit - req.t_submit),
                                 prefill_ms=1e3 * (t - req.t_admit)):
                pass
        if len(req.generated) >= req.max_new_tokens or \
                (req.eos_id is not None and tok == req.eos_id):
            self._finish(i, req)
            return True
        return False

    # ------------------------------------------------------------------ step
    def _dispatch(self, kernel_calls, chunk: int, prefill: bool = False):
        """Count one program call and open its ``serve.dispatch`` span.
        ``kernel_calls``: the ``(lengths, n_valid)`` of each pass of the
        call through the paged kernel (one, or one per step of the draft's
        proposal scan), each ``chunk`` tokens wide. Live pages cover each
        valid slot's cached plus new tokens; walked pages are what the
        kernel's grid visits: each valid slot's live pages rounded up to
        whole blocks of the kernel's ``block_pages``. ``prefill``: a
        chunk-program call, whose rows count too."""
        P = self.pool.page_size
        rows, nP = self.pool.tables.shape
        k_pages = self.pool.pages["k_pages"]
        cfg = self.model.cfg
        k = block_pages(cfg.n_heads // cfg.n_kv_heads * chunk,
                        k_pages.shape[-1], k_pages.dtype.itemsize, P, nP)
        live = walked = 0
        for lengths, n_valid in kernel_calls:
            pages = -(-(lengths + n_valid) // P)[n_valid > 0]
            live += int(np.sum(pages))
            walked += int(np.sum(-(-pages // k))) * k
        self.stats.pages_live += live
        self.stats.pages_walked += walked
        rows_valid = rows_computed = 0
        if prefill:
            rows_valid = int(kernel_calls[0][1].sum())
            rows_computed = rows * self.prefill_chunk
            self.stats.prefill_rows_valid += rows_valid
            self.stats.prefill_rows_computed += rows_computed
        return TraceAnnotation("serve.dispatch", pages_live=live,
                               pages_walked=walked, rows_valid=rows_valid,
                               rows_computed=rows_computed)

    def _run_prefill_chunks(self) -> None:
        C = self.prefill_chunk
        with TraceAnnotation("serve.prepare"):
            idx = [i for i, s in enumerate(self.slots)
                   if s is not None and s.pos < len(s.prompt)]
            if not idx:
                return
            tokens = np.zeros((self.n_slots, C), np.int32)
            n_valid = np.zeros((self.n_slots,), np.int32)
            for i in idx:
                s = self.slots[i]
                chunk = s.prompt[s.pos:s.pos + C]
                tokens[i, :len(chunk)] = chunk
                n_valid[i] = len(chunk)
        lengths = self.pool.lengths
        if self.speculative:
            # keep the draft cache in lockstep: same tokens into the draft
            # pool, logits discarded — this is what makes a later sharer's
            # draft cache warm over shared prefix pages too
            with self._dispatch([(lengths, n_valid)], C):
                _, self.pool.draft_pages = self._draft_fn(
                    self.draft_params, tokens, self.pool.draft_pages,
                    self.pool.tables, lengths, n_valid)
        with self._dispatch([(lengths, n_valid)], C, prefill=True):
            logits, self.pool.pages = self._step_fn(
                self.params, tokens, self.pool.pages,
                self.pool.tables, lengths, n_valid)
        with TraceAnnotation("serve.fetch"):
            logits = np.asarray(logits)
        with TraceAnnotation("serve.sample"):
            self._sample_prefill(idx, n_valid, logits)

    def _sample_prefill(self, idx, n_valid, logits) -> None:
        for i in idx:
            s = self.slots[i]
            s.pos += int(n_valid[i])
            self.pool.lengths[i] += int(n_valid[i])
            if self.prefix_sharing:
                self.pool.register_prefix(i, s.req.tenant, s.prompt, s.pos)
            if s.pos == len(s.prompt):
                # prefill done: the chunk's last-valid logits give the first
                # generated token (same source as the wave's prefill logits)
                if self.trace_logits:
                    self.logit_trace.setdefault(s.req.rid, []).append(
                        logits[i].copy())
                tok = int(np.argmax(logits[i]))
                if not self._append(i, tok):
                    s.pending = tok

    def _run_decode(self) -> None:
        with TraceAnnotation("serve.prepare"):
            idx = [i for i, s in enumerate(self.slots)
                   if s is not None and s.pending is not None]
            if not idx:
                return
            tokens = np.zeros((self.n_slots, 1), np.int32)
            n_valid = np.zeros((self.n_slots,), np.int32)
            for i in idx:
                tokens[i, 0] = self.slots[i].pending
                n_valid[i] = 1
        with self._dispatch([(self.pool.lengths, n_valid)], 1):
            logits, self.pool.pages = self._step_fn(
                self.params, tokens, self.pool.pages,
                self.pool.tables, self.pool.lengths, n_valid)
        with TraceAnnotation("serve.fetch"):
            logits = np.asarray(logits)
        with TraceAnnotation("serve.sample"):
            self._sample_decode(idx, logits)

    def _sample_decode(self, idx, logits) -> None:
        for i in idx:
            self.pool.lengths[i] += 1
            if self.trace_logits:
                self.logit_trace.setdefault(self.slots[i].req.rid, []).append(
                    logits[i].copy())
            tok = int(np.argmax(logits[i]))
            if not self._append(i, tok):
                self.slots[i].pending = tok

    def _run_spec_decode(self) -> None:
        """One speculative tick for every decode-ready slot: ``k_eff - 1``
        draft proposals, one combined chunk-shaped verify, greedy accept,
        in-kernel rollback of the rejected tail in both pools.

        ``k_eff = min(spec_k, remaining budget)`` per slot: the bucketed
        allocation covers exactly prompt + max_new tokens, so speculating
        past the budget would write K/V past the slot's page capacity."""
        k = self.spec_k
        with TraceAnnotation("serve.prepare"):
            idx = [i for i, s in enumerate(self.slots)
                   if s is not None and s.pending is not None]
            if not idx:
                return
            k_eff = {i: min(k, self.slots[i].req.max_new_tokens
                            - len(self.slots[i].req.generated)) for i in idx}
            base = self.pool.lengths.copy()
            props = {i: [self.slots[i].pending] for i in idx}
            t0 = np.zeros((self.n_slots,), np.int32)
            keff_arr = np.zeros((self.n_slots,), np.int32)
            for i in idx:
                t0[i] = props[i][0]
                keff_arr[i] = k_eff[i]
        # draft step j writes one token for slots with j < k_eff - 1
        with self._dispatch([(base + j, (j < keff_arr - 1).astype(np.int32))
                             for j in range(k - 1)], 1):
            drafted, self.pool.draft_pages = self._propose_fn(
                self.draft_params, self.pool.draft_pages, self.pool.tables,
                base, t0, keff_arr)
        with TraceAnnotation("serve.fetch"):
            # (n_slots, k-1); cols >= k_eff-1 junk
            drafted = np.asarray(drafted)
        with TraceAnnotation("serve.prepare"):
            for i in idx:
                props[i] += [int(t) for t in drafted[i, :k_eff[i] - 1]]
            tokens = np.zeros((self.n_slots, k), np.int32)
            n_valid = np.zeros((self.n_slots,), np.int32)
            for i in idx:
                tokens[i, :k_eff[i]] = props[i]
                n_valid[i] = k_eff[i]
        with self._dispatch([(base, n_valid)], k):
            vlogits, self.pool.pages = self._verify_fn(
                self.params, tokens, self.pool.pages,
                self.pool.tables, base, n_valid)
        with TraceAnnotation("serve.fetch"):
            vlogits = np.asarray(vlogits)  # (n_slots, k, V)
        with TraceAnnotation("serve.sample"):
            self._accept(idx, k_eff, base, props, vlogits)

    def _accept(self, idx, k_eff, base, props, vlogits) -> None:
        """Greedy accept of each slot's proposals against the verifier's
        argmax, then rollback of the rejected tail."""
        for i in idx:
            ke, p = k_eff[i], props[i]
            # row j scores the token following p[j]; g[j] is therefore the
            # ground-truth stream, exactly what sequential decode would emit
            g = [int(np.argmax(vlogits[i, j])) for j in range(ke)]
            a = 0
            while a < ke - 1 and p[a + 1] == g[a]:
                a += 1
            self.stats.spec_proposed += ke - 1
            self.stats.spec_accepted += a
            done = False
            for j in range(a + 1):
                if self.trace_logits:
                    self.logit_trace.setdefault(
                        self.slots[i].req.rid, []).append(vlogits[i, j].copy())
                if self._append(i, g[j]):
                    done = True  # _finish released the slot: no rollback —
                    break        # its fresh pages are refcount-0 and will be
                #                  zeroed by the next admission as usual
            if not done:
                final = int(base[i]) + a + 1
                self.pool.rollback(i, final, int(base[i]) + ke)
                self.pool.lengths[i] = final
                self.slots[i].pending = g[a]

    def step(self) -> None:
        """One scheduler tick: admit into free slots, decode (or
        speculatively decode) every ready slot, advance every mid-prefill
        slot by one chunk. Decode runs before the chunk pass so a slot
        completing prefill starts decoding next tick — at most one token
        per slot per tick in plain mode (the wave loop's cadence, which is
        what makes the stats comparable), up to ``spec_k`` in speculative
        mode.

        Utilization accounting also mirrors the wave loop exactly: a tick
        that HARVESTS tokens is charged a full batch of slots (idle and
        mid-prefill slots are the measured tax) — times ``spec_k`` in
        speculative mode, where every slot had k chances; prefill compute
        itself is free, like the wave's uncharged prefill call."""
        with TraceAnnotation("serve.tick"):
            self.clock += 1
            before = self.stats.useful_tokens
            resets = self.stats.state_resets
            with TraceAnnotation("serve.admit") as span:
                self._admit()
                span.set_metadata(
                    state_resets=self.stats.state_resets - resets)
            with TraceAnnotation("serve.decode"):
                if self.speculative:
                    self._run_spec_decode()
                else:
                    self._run_decode()
            with TraceAnnotation("serve.prefill"):
                self._run_prefill_chunks()
            if self.stats.useful_tokens > before:
                self.stats.decode_steps += 1
                self.stats.slot_tokens += self.n_slots * (
                    self.spec_k if self.speculative else 1)

    def run_until_drained(self, max_steps: int = 100_000) -> ServerStats:
        while (self.queued or any(s is not None for s in self.slots)) \
                and self.clock < max_steps:
            self.step()
        leftover = self.queued + sum(s is not None for s in self.slots)
        self.stats.drained = leftover == 0
        if leftover:
            warnings.warn(
                f"run_until_drained stopped at max_steps={max_steps} with "
                f"{leftover} requests still in flight — stats cover a "
                f"truncated trace", RuntimeWarning, stacklevel=2)
        return self.stats
