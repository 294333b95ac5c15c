"""The general request generator: deterministic from the seed, the same set
of sizes and gaps for every seed, and the stated length distributions."""
import json
from pathlib import Path

import numpy as np

from bench.lib import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def test_open_loop_is_deterministic_from_the_seed():
    a = traffic.open_loop(mix("chat"), 30, 2**33 + 5, 1000)
    b = traffic.open_loop(mix("chat"), 30, 2**33 + 5, 1000)
    assert [(r.due, r.max_new, r.tenant) for r in a] == \
        [(r.due, r.max_new, r.tenant) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_open_loop_seeds_reorder_the_same_work():
    m = mix("chat")
    a = traffic.open_loop(m, 30, 1, 1000)
    b = traffic.open_loop(m, 30, 2, 1000)
    assert len(a) == len(b) == round(m["rate_per_s"] * 30)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    gaps_a = np.diff([0.0] + [r.due for r in a])
    gaps_b = np.diff([0.0] + [r.due for r in b])
    assert np.allclose(np.sort(gaps_a), np.sort(gaps_b), rtol=0, atol=1e-9)
    assert 0 < a[0].due and a[-1].due < 30
    assert all(x.due < y.due for x, y in zip(a, a[1:]))


def test_chat_lengths_follow_the_stated_lognormal():
    m = mix("chat")
    sizes = traffic.quantile_sizes(m["prompt_tokens"], 2000)
    assert sizes.min() >= 32 and sizes.max() <= 1536
    assert abs(np.median(sizes) - 384) <= 2
    assert abs(np.percentile(sizes, 95) - 1280) / 1280 < 0.02
    out = traffic.quantile_sizes(m["output_tokens"], 2000)
    assert out.min() >= 8 and out.max() <= 512
    assert abs(np.median(out) - 96) <= 1


def test_poisson_gaps_have_the_stated_rate():
    m = dict(mix("chat"), rate_per_s=10.0)
    reqs = traffic.open_loop(m, 100, 3, 50)
    gaps = np.diff([0.0] + [r.due for r in reqs])
    assert len(reqs) == 1000
    assert abs(gaps.mean() - 0.1) < 0.005
    assert abs(np.median(gaps) - np.log(2) / 10) < 0.01  # exponential median


def test_closed_loop_blocks_permute_one_set_of_sizes():
    m = mix("decode")
    c = m["concurrency"]
    reqs = traffic.closed_loop(m, 9, 32064, blocks=3)
    assert len(reqs) == 3 * c
    first = sorted(len(r.prompt) for r in reqs[:c])
    for b in range(3):
        blk = reqs[b * c:(b + 1) * c]
        assert sorted(len(r.prompt) for r in blk) == first
        assert all(512 <= len(r.prompt) <= 1024 for r in blk)
        assert all(256 <= r.max_new <= 512 for r in blk)
    assert np.allclose(np.mean(first), 768, atol=1)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 32064 for r in reqs)
