"""The operation and byte counts the rooflines and MFUs divide by, against
hand counts at a tiny size, and the benchmark's weight tree against the
program's parameter tree."""
import importlib.util
import math
from pathlib import Path

import numpy as np

from bench.lib import model, readers, weights

TINY = model.Sizes(name="tiny", n_layers=2, d_model=8, n_heads=4,
                   n_kv_heads=2, d_head=2, d_ff=16, vocab=32, qkv_bias=True,
                   tied=True, rope_theta=1e4, norm_eps=1e-6)
METRICS = Path(__file__).resolve().parents[1] / "metrics"


def test_parameter_counts_by_hand():
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 8x16 x3
    assert TINY.layer_matmul_params == 64 + 32 + 32 + 64 + 3 * 128
    assert TINY.layer_params == 576 + (8 + 4 + 4) + 2 * 8
    assert TINY.params == 2 * 608 + 32 * 8 + 8       # tied: no head matrix
    assert TINY.matmul_params == 2 * 576 + 256      # the tied head counts


def test_train_flops_per_token_by_hand():
    S = 4
    attn = 4 * 2 * 4 * 2 * (S + 1) / 2            # 4 L Hq Dh x mean context
    assert TINY.train_flops_per_token(S) == 6 * 1408 + 3 * attn


def test_serving_call_counts_by_hand():
    # slot 0: 3 new tokens after 5 cached; slot 1 idle; slot 2: 1 after 16
    n_valid, q_start = np.array([3, 0, 1]), np.array([5, 9, 16])
    ctx = (6 + 7 + 8) + 17
    want = 2 * 2 * 576 * 4 + 4 * 2 * 4 * 2 * ctx + 2 * 2 * 256
    assert readers.call_flops(TINY, n_valid, q_start) == want
    kv_tok = 2 * 4 * 2 * 2 * 2                       # k,v x f32 x L x Hkv x Dh
    pages = math.ceil(8 / 4) * 4 + math.ceil(17 / 4) * 4
    assert readers.live_kv_bytes(TINY, n_valid, q_start, 4) == pages * kv_tok
    assert readers.call_bytes(TINY, n_valid, q_start, 4) == \
        4 * (2 * 608 + 256 + 8) + pages * kv_tok
    assert readers.call_bytes(TINY, np.zeros(3), q_start, 4) == 0


def test_weight_tree_is_the_program_parameter_tree():
    import jax

    from repro.models.registry import build_model

    for tied in (True, False):
        s = model.Sizes(**{**TINY.__dict__, "tied": tied})
        prog = jax.eval_shape(build_model(model.program_config(s)).init,
                              jax.random.PRNGKey(0))
        ours = jax.eval_shape(lambda: weights.make(s, 3))
        assert jax.tree.structure(prog) == jax.tree.structure(ours)
        assert [a.shape for a in jax.tree.leaves(prog)] == \
            [a.shape for a in jax.tree.leaves(ours)]
        n = sum(math.prod(a.shape) for a in jax.tree.leaves(ours))
        assert n == s.params


def test_packed_size_matches_the_program_layout():
    import jax

    from repro.core import flatbuf

    spec = importlib.util.spec_from_file_location(
        "roofline", METRICS / "dp_pallas_roofline.train.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    tree = jax.eval_shape(lambda: weights.make(TINY, 1))
    assert mod.packed_size(TINY) == flatbuf.layout_of(tree).total


def test_weights_are_deterministic_from_large_seeds():
    a = weights.make(TINY, 2**32 + 17)
    b = weights.make(TINY, 2**32 + 17)
    c = weights.make(TINY, 2**32 + 18)
    import jax
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(a["embed"], c["embed"])
