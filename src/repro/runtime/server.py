"""Confidential serving runtime: length-bucketed wave batching.

The contiguous KV cache (models/attention.py) advances all batch rows in
lockstep (one length per layer), so the scheduler batches requests into
*waves*: requests are bucketed by prompt length, a wave of up to ``max_batch``
same-length prompts is prefilled together, then decoded until every member
finishes (early finishers are masked out, their slots produce dead tokens
until the wave drains — the classic static-batching trade, measured by the
``utilization`` stat). Length bucketing is the standard mitigation and keeps
one compiled prefill/decode graph per bucket shape.

Every wave gets a *fresh* cache: cross-request leakage through cache reuse is
structurally impossible (the serving-side analogue of the paper's R2
state-isolation requirement — a recycled slot never exposes a previous
request's K/V).
"""
from __future__ import annotations

import collections
import warnings
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    eos_id: Optional[int] = None
    generated: list = field(default_factory=list)
    done: bool = False
    submit_step: int = 0   # scheduler clock at submission
    finish_step: int = -1  # scheduler clock when the last token landed
    # admission-control identity (the data owner / API key the request
    # arrived under); None = untenanted, exempt from per-tenant slot caps
    tenant: Optional[str] = None
    # scheduling priority: under page-pool pressure the ContinuousServer may
    # preempt the lowest-priority running slot to admit a STRICTLY
    # higher-priority request (the preempted request is re-queued at its
    # original position and restored by recompute — token-identical output)
    priority: int = 0
    # host-clock stamps (``time.perf_counter()``) set by the
    # ContinuousServer: submitted, first admitted to a slot, first token
    # on the host
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first_token: Optional[float] = None


@dataclass
class ServerStats:
    waves: int = 0
    decode_steps: int = 0
    useful_tokens: int = 0
    slot_tokens: int = 0  # decode_steps x batch slots
    # per-request latency in scheduler steps (finish - submit), appended at
    # completion — the comparable tail metric across wave and continuous
    latencies: list = field(default_factory=list)
    # False when run_until_drained stopped on its step budget with requests
    # still queued or in flight — the latency percentiles then describe a
    # TRUNCATED trace (survivorship-biased: the slow tail never finished)
    drained: bool = True
    # prefix sharing: prompt tokens whose prefill was skipped because their
    # pages were mapped read-only from the tenant's prefix index
    shared_prompt_tokens: int = 0
    # speculative decoding: draft proposals made / accepted by the verifier
    spec_proposed: int = 0
    spec_accepted: int = 0
    # graceful degradation: slots evicted under pool pressure to admit a
    # higher-priority request (each restored later by recompute)
    preemptions: int = 0
    # prefill chunk calls: rows holding prompt tokens / rows the chunk
    # program computed (n_slots x chunk per call)
    prefill_rows_valid: int = 0
    prefill_rows_computed: int = 0
    # paged-attention calls: table pages covering each valid slot's tokens
    # after the call / table pages the kernel's grid visits
    pages_live: int = 0
    pages_walked: int = 0
    # slots whose recurrent state was zeroed on admission (families whose
    # pool holds one)
    state_resets: int = 0

    @property
    def utilization(self) -> float:
        return self.useful_tokens / max(self.slot_tokens, 1)

    @property
    def acceptance_rate(self) -> float:
        return self.spec_accepted / max(self.spec_proposed, 1)

    def _pct(self, q: float) -> float:
        if not self.latencies:
            return 0.0
        xs = sorted(self.latencies)
        return float(xs[min(len(xs) - 1, int(q * len(xs)))])

    @property
    def p50_latency_steps(self) -> float:
        return self._pct(0.50)

    @property
    def p99_latency_steps(self) -> float:
        return self._pct(0.99)


class WaveServer:
    """Batched prefill + decode waves over length-bucketed request queues."""

    def __init__(self, model, params, max_batch: int = 8,
                 max_len: int = 512, greedy: bool = True):
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.buckets: dict[int, collections.deque[Request]] = \
            collections.defaultdict(collections.deque)
        self.stats = ServerStats()
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)

    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(f"request {req.rid} exceeds max_len {self.max_len}")
        req.submit_step = self.stats.decode_steps  # queueing counts as latency
        self.buckets[len(req.prompt)].append(req)

    def _next_wave(self) -> list[Request]:
        if not self.buckets:
            return []
        # largest bucket first (best packing)
        plen = max(self.buckets, key=lambda k: len(self.buckets[k]))
        q = self.buckets[plen]
        wave = [q.popleft() for _ in range(min(self.max_batch, len(q)))]
        if not q:
            del self.buckets[plen]
        return wave

    def _run_wave(self, wave: list[Request]) -> None:
        B = len(wave)
        plen = len(wave[0].prompt)
        budget = max(r.max_new_tokens for r in wave)
        cache = self.model.init_cache(B, plen + budget)  # fresh: R2 isolation

        prompts = jnp.asarray(np.stack([r.prompt for r in wave]))
        logits, cache = self._prefill(self.params, {"tokens": prompts}, cache)
        tok = jnp.argmax(logits, axis=-1)[:, None]

        alive = np.ones(B, bool)
        for step in range(budget):
            # tick the clock first so the step harvesting a request's last
            # token is included in its latency
            self.stats.decode_steps += 1
            self.stats.slot_tokens += B
            toks = np.asarray(tok)
            for i, r in enumerate(wave):
                if not alive[i]:
                    continue
                t = int(toks[i, 0])
                r.generated.append(t)
                self.stats.useful_tokens += 1
                if len(r.generated) >= r.max_new_tokens or \
                        (r.eos_id is not None and t == r.eos_id):
                    r.done = True
                    alive[i] = False
                    r.finish_step = self.stats.decode_steps
                    self.stats.latencies.append(
                        r.finish_step - r.submit_step)
            if not alive.any() or step == budget - 1:
                break
            logits, cache = self._decode(self.params, {"tokens": tok}, cache)
            tok = jnp.argmax(logits, axis=-1)[:, None]
        for r in wave:
            r.done = True
        self.stats.waves += 1

    def run_until_drained(self, max_waves: int = 1000) -> ServerStats:
        while self.buckets and self.stats.waves < max_waves:
            wave = self._next_wave()
            if not wave:
                break
            self._run_wave(wave)
        self.stats.drained = not self.buckets
        if self.buckets:
            leftover = sum(len(q) for q in self.buckets.values())
            warnings.warn(
                f"run_until_drained stopped at max_waves={max_waves} with "
                f"{leftover} requests still queued — stats cover a "
                f"truncated trace", RuntimeWarning, stacklevel=2)
        return self.stats
