"""The one general request generator. A mix file (``bench/traffic/<mix>.json``)
gives its parameters; the seed only orders a fixed set of sizes and gaps and
draws the token ids, so every seed asks for the same work.

Length distributions: ``{"dist": "lognormal", "median", "p95", "min",
"max"}`` or ``{"dist": "uniform", "min", "max"}``. For ``n`` requests the
sizes are the distribution's quantiles at ``(i + 0.5) / n``, clipped to
``[min, max]`` and permuted by the seed. Open-loop arrivals are Poisson at
``rate_per_s``: the gaps are the exponential's quantiles, permuted likewise.
A closed loop draws its requests in blocks of ``concurrency``, each block a
new permutation of the same sizes.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    due: float            # seconds after the window opens (open loop)
    prompt: np.ndarray    # int32 token ids
    max_new: int
    tenant: str


def quantile_sizes(spec: dict, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        mu = np.log(spec["median"])
        sig = np.log(spec["p95"] / spec["median"]) / NormalDist().inv_cdf(0.95)
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        v = np.exp(mu + sig * z)
    elif spec["dist"] == "uniform":
        v = lo + (hi - lo) * q
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(int(seed)))


def open_loop(mix: dict, seconds: float, seed: int, vocab: int) -> list:
    """``round(rate * seconds)`` requests whose arrivals fill the window."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = _rng(seed)
    p_len = rng.permutation(quantile_sizes(mix["prompt_tokens"], n))
    o_len = rng.permutation(quantile_sizes(mix["output_tokens"], n))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / (gaps.sum() + gaps.mean())  # the last lands inside
    due = np.cumsum(rng.permutation(gaps))
    tenants = int(mix.get("tenants", 1))
    return [Planned(i, float(due[i]),
                    rng.integers(0, vocab, int(p_len[i])).astype(np.int32),
                    int(o_len[i]), f"tenant-{i % tenants}")
            for i in range(n)]


def closed_loop(mix: dict, seed: int, vocab: int, blocks: int = 16) -> list:
    """``blocks`` x ``concurrency`` requests, in order of submission."""
    c = int(mix["concurrency"])
    rng = _rng(seed)
    p_set = quantile_sizes(mix["prompt_tokens"], c)
    o_set = quantile_sizes(mix["output_tokens"], c)
    tenants = int(mix.get("tenants", 1))
    out = []
    for b in range(blocks):
        for p, o in zip(rng.permutation(p_set), rng.permutation(o_set)):
            i = len(out)
            out.append(Planned(i, 0.0,
                               rng.integers(0, vocab, int(p)).astype(np.int32),
                               int(o), f"tenant-{i % tenants}"))
    return out
