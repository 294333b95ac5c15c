"""The Zamba2 cell on the CPU at a tiny size: a sound run is correct and a
served token altered is not; the counts the Zamba2 readers divide by agree
with the program's parameter tree at the configuration's published widths."""
import dataclasses
import json
import math
from pathlib import Path

import jax

from bench.lib import harness, serve_zamba2, zamba2

BENCH = Path(__file__).resolve().parents[1]
CELL = "serve-docqa.zamba2-7b"


def tiny_sizes(ctx):
    s = zamba2.load("zamba2-7b-instruct.12l")
    return dataclasses.replace(
        s, n_layers=6, hybrid_ids=(2, 4), d_model=64, n_heads=4, d_head=32,
        d_ff=128, vocab=512, ssm_state=16, headdim=16, chunk=16,
        adapter_rank=8)


def tiny_mix():
    mix = json.loads((BENCH / "traffic" / "docqa.json").read_text())
    mix.update(concurrency=3, check_requests=3)
    mix["prompt_tokens"].update(min=20, max=60)
    mix["output_tokens"].update(min=4, max=8)
    mix["server"].update(max_batch=3, max_len=128, prefill_chunk=16)
    return mix


def run(monkeypatch, **kw):
    monkeypatch.setattr(serve_zamba2, "sizes", tiny_sizes)
    return harness.run_cell(CELL, 2**33 + 7, 1.5, False, require_chip=False,
                            mix_override=tiny_mix(), **kw)


def test_sound_run_is_correct(monkeypatch):
    r = run(monkeypatch)
    assert r["correct"], r["checks"]
    assert r["_diag"]["records"]["zamba2_config"] == "zamba2-7b-instruct.12l"
    assert r["metrics"]["serve_itl_p95_ms"]["value"] > 0


def test_altered_token_is_not_correct(monkeypatch):
    r = run(monkeypatch, fault="token_altered")
    assert not r["correct"], r["checks"]


def test_counts_match_the_program():
    from repro.configs.base import param_count
    from repro.models.registry import build_model

    s = zamba2.load("zamba2-7b-instruct.12l")
    cfg = zamba2.program_config(s)
    tree = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(tree))
    assert n == s.params == param_count(cfg)
    shapes = jax.tree.leaves(zamba2.shapes(s),
                             is_leaf=lambda x: isinstance(x, tuple))
    assert [x.shape for x in jax.tree.leaves(tree)] == shapes
    # the per-token split of the 12-layer cut: 941 M in the 12 mixers,
    # 702 M in the two invocations
    assert round(12 * s.mamba_matmul_params / 1e6) == 941
    assert round(2 * s.shared_matmul_params / 1e6) == 702
