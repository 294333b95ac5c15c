"""Packed flat-buffer DP engine: pack/unpack round-trips, packed-vs-per-leaf
numerical parity (clipped sums, per-example norms, masked aggregates under
fixed keys) and the fused-kernel bit-consistency guarantees."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import PrivacyConfig
from repro.core import barrier as barrier_mod
from repro.core import flatbuf, masking
from repro.core.noise_correction import init_state
from repro.kernels.dp_clip import ref as dref
from repro.kernels.dp_fused import ops as fops
from repro.kernels.dp_fused import ref as fref

KEY_R = jnp.array([11, 22], jnp.uint32)
KEY_XI = jnp.array([33, 44], jnp.uint32)
KEY_P = jnp.array([55, 66], jnp.uint32)


def mixed_tree(key, B=0):
    """Deliberately awkward leaves: unaligned sizes, a scalar, bf16."""
    ks = jax.random.split(key, 4)
    lead = (B,) if B else ()
    return {
        "w": jax.random.normal(ks[0], lead + (3, 5)),
        "b": jax.random.normal(ks[1], lead + (300,)).astype(jnp.bfloat16),
        "s": jax.random.normal(ks[2], lead),
        "m": jax.random.normal(ks[3], lead + (2, 7, 9)),
    }


# ---------------------------------------------------------------------------
# layout + round trip


def test_layout_alignment_and_cache():
    t = mixed_tree(jax.random.PRNGKey(0))
    lay = flatbuf.layout_of(t)
    assert all(o % flatbuf.LANE == 0 for o in lay.offsets)
    assert lay.total % flatbuf.ALIGN == 0
    assert lay.n_params == 15 + 300 + 1 + 126
    # same structure -> same cached layout object
    t2 = mixed_tree(jax.random.PRNGKey(1))
    assert flatbuf.layout_of(t2) is lay


def test_pack_unpack_roundtrip_unbatched_and_batched():
    for B in (0, 8, 5):
        t = mixed_tree(jax.random.PRNGKey(2), B=B)
        lay = flatbuf.layout_of(t, batch_dims=1 if B else 0)
        buf = flatbuf.pack(lay, t)
        assert buf.dtype == jnp.float32
        assert buf.shape == ((B, lay.total) if B else (lay.total,))
        back = flatbuf.unpack(lay, buf)
        for k in t:
            assert back[k].dtype == t[k].dtype
            np.testing.assert_array_equal(
                np.asarray(back[k], np.float32), np.asarray(t[k], np.float32))


def test_padding_is_exactly_zero():
    t = mixed_tree(jax.random.PRNGKey(3))
    lay = flatbuf.layout_of(t)
    buf = np.asarray(flatbuf.pack(lay, t))
    mask = np.ones(lay.total, bool)
    for off, size in zip(lay.offsets, lay.sizes):
        mask[off:off + size] = False
    assert (buf[mask] == 0.0).all()


def test_pack_works_under_vmap():
    t = mixed_tree(jax.random.PRNGKey(4), B=6)
    lay = flatbuf.layout_of(t, batch_dims=1)
    stacked = jax.vmap(lambda tt: flatbuf.pack(lay, tt))(t)
    np.testing.assert_array_equal(np.asarray(stacked),
                                  np.asarray(flatbuf.pack(lay, t)))


def test_hypothesis_roundtrip_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(deadline=None, max_examples=25)
    @given(st.lists(st.tuples(st.integers(1, 4), st.integers(1, 37)),
                    min_size=1, max_size=6))
    def prop(shapes):
        tree = {f"l{i}": jnp.arange(a * b, dtype=jnp.float32).reshape(a, b) - 7.0
                for i, (a, b) in enumerate(shapes)}
        lay = flatbuf.layout_of(tree)
        back = flatbuf.unpack(lay, flatbuf.pack(lay, tree))
        for k in tree:
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(tree[k]))

    prop()


# ---------------------------------------------------------------------------
# packed vs per-leaf parity: clipped sums + per-example norms


def test_clip_and_sum_packed_matches_perleaf():
    """The packed tree clip against the per-example reference built leaf by
    leaf from the kernels/dp_clip oracles."""
    t = mixed_tree(jax.random.PRNGKey(5), B=8)
    flat = [g.reshape(8, -1) for g in jax.tree.leaves(t)]
    total = sum(dref.per_example_sumsq_ref(g) for g in flat)
    scale = dref.clip_scales(total, 0.7)
    s_pl = [dref.clip_accumulate_ref(g, scale) for g in flat]
    n_pl = jnp.sqrt(total)
    s_pk, n_pk = fops.packed_clip_and_sum(t, 0.7)
    np.testing.assert_allclose(np.asarray(n_pk), np.asarray(n_pl), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_pk), s_pl):
        b = b.reshape(a.shape)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_clip_sum_kernel_pallas_matches_jnp():
    t = mixed_tree(jax.random.PRNGKey(6), B=8)
    lay = flatbuf.layout_of(t, batch_dims=1)
    packed = flatbuf.pack(lay, t)
    s_j, n_j = fops.clip_sum_packed(packed, 0.9, impl="jnp")
    s_p, n_p = fops.clip_sum_packed(packed, 0.9, impl="pallas")
    np.testing.assert_allclose(np.asarray(n_p), np.asarray(n_j), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_j),
                               rtol=1e-4, atol=1e-5)


def test_clip_mask_kernel_bit_consistent_any_blocking():
    g = jax.random.normal(jax.random.PRNGKey(7), (4096,))
    args = (0.7, KEY_R, KEY_XI, KEY_P, jnp.int32(2), 4, 1.5, 8.0, 0.6)
    ref_out = fref.clip_mask_ref(g, *args)
    from repro.kernels.dp_fused.dp_fused import clip_mask_pallas
    for block in (1024, 2048, 4096):
        pal = clip_mask_pallas(g, *args, block_d=block, interpret=True)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref_out),
                                   atol=1e-5)


def _noise_batch_case(n):
    """Per-silo scale/gate vectors with a few dropped silos."""
    gates = np.ones(n, np.float32)
    gates[1::5] = 0.0
    noise_scales = jnp.asarray((0.3 + 0.01 * np.arange(n)) * gates,
                               jnp.float32)
    lam_gates = jnp.asarray(0.7 * gates, jnp.float32)
    return noise_scales, lam_gates, jnp.float32(0.41)


@pytest.mark.parametrize("n", [4, 11, 44])
def test_noise_batch_ref_bit_matches_silo_fold(n):
    """The one-launch batched construction == the sequential left fold of
    per-silo clip_mask_ref noise shares, BIT-IDENTICAL at every n (including
    partial participation gates and the chunked >8-silo path — the chunk
    loop must stay unrolled or XLA's loop-body FMA contraction breaks
    this)."""
    P = 2048
    g = jax.random.normal(jax.random.PRNGKey(1), (P,))
    zeros = jnp.zeros((P,), jnp.float32)
    noise_scales, lam_gates, s_prev = _noise_batch_case(n)
    expect = g.astype(jnp.float32)
    for i in range(n):
        expect = expect + fref.clip_mask_ref(
            zeros, 1.0, KEY_XI, KEY_XI, KEY_P, jnp.int32(i), n, 1.0, 0.0,
            lam_gates[i], use_pairwise=False, use_prev=True,
            noise_scale=noise_scales[i], prev_noise_scale=s_prev)
    got = fref.noise_batch_ref(g, KEY_XI, KEY_P, noise_scales, lam_gates,
                               s_prev)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))
    # lam = 0 everywhere: the prev-stream draw may be skipped entirely
    no_prev = fref.noise_batch_ref(g, KEY_XI, KEY_P, noise_scales,
                                   jnp.zeros((n,), jnp.float32), s_prev,
                                   use_prev=False)
    expect_np = g.astype(jnp.float32)
    for i in range(n):
        expect_np = expect_np + fref.clip_mask_ref(
            zeros, 1.0, KEY_XI, KEY_XI, KEY_P, jnp.int32(i), n, 1.0, 0.0,
            0.0, use_pairwise=False, use_prev=False,
            noise_scale=noise_scales[i], prev_noise_scale=s_prev)
    np.testing.assert_array_equal(np.asarray(no_prev), np.asarray(expect_np))


@pytest.mark.parametrize("n", [4, 44])
def test_noise_batch_pallas_matches_ref_any_blocking(n):
    """Single-launch Pallas variant against the jnp oracle for several
    blockings (same 1e-5 tolerance as the other fused kernels: the jitted
    kernel graph may FMA-contract the share multiply-adds)."""
    from repro.kernels.dp_fused.dp_fused import noise_batch_pallas

    P = 4096
    g = jax.random.normal(jax.random.PRNGKey(2), (P,))
    noise_scales, lam_gates, s_prev = _noise_batch_case(n)
    ref_out = fref.noise_batch_ref(g, KEY_XI, KEY_P, noise_scales, lam_gates,
                                   s_prev)
    for rows in (8, 16, 32):
        pal = noise_batch_pallas(g, KEY_XI, KEY_P, noise_scales, lam_gates,
                                 s_prev, block_rows=rows, interpret=True)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref_out),
                                   atol=1e-5)


@pytest.mark.parametrize("n", [2, 44])
@pytest.mark.parametrize("rows,block_rows,strip_rows", [
    (40, 16, 8),     # 2 full blocks + a ragged one of 8 rows
    (72, 64, 16),    # ragged last block of 8 rows, 16-row strips
    (88, 32, 16),    # 11 x 8 rows, like the real 1,812,088: no power of 2
    (24, 2048, 16),  # one block of the whole buffer; strips fall to 8
])
def test_noise_batch_pallas_ragged_blocks_bit_match_ref(n, rows, block_rows,
                                                        strip_rows):
    """The (P // 128, 128) view in (block_rows, 128) blocks, walked in
    strips, with a ragged last block: every value equals the jitted oracle
    and the 8-row geometry bit for bit (counters are global indices)."""
    from repro.kernels.dp_fused.dp_fused import noise_batch_pallas

    P = rows * 128
    g = jax.random.normal(jax.random.PRNGKey(rows), (P,))
    args = (g, KEY_XI, KEY_P) + _noise_batch_case(n)
    ref_out = jax.jit(fref.noise_batch_ref)(*args)
    pal = noise_batch_pallas(*args, block_rows=block_rows,
                             strip_rows=strip_rows, interpret=True)
    eight = noise_batch_pallas(*args, block_rows=8, strip_rows=8,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(ref_out))
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(eight))


# ---------------------------------------------------------------------------
# masked aggregates under fixed keys


def test_packed_masks_telescope_to_aggregate_noise():
    """sum_i packed-mask(g=0) == the aggregate_noise_from_streams helper
    (r-terms telescope; xi streams sum to N(0, sigma_c^2))."""
    n, sigma_c, b = 6, 2.0, 8.0
    t = mixed_tree(jax.random.PRNGKey(8))
    keys = barrier_mod.BarrierKeys(KEY_R, KEY_XI, KEY_P)
    zeros = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), t)
    total = None
    for i in range(n):
        m = masking.pairwise_mask_tree(zeros, KEY_R, KEY_XI, jnp.int32(i), n,
                                       sigma_c, b)
        total = m if total is None else jax.tree.map(jnp.add, total, m)
    expect = barrier_mod.aggregate_noise_from_streams(t, keys, n, sigma_c)
    for a, b_ in zip(jax.tree.leaves(total), jax.tree.leaves(expect)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-3)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_pairwise_mask_tree_draws_the_kernel_streams(impl):
    """Each silo's tree mask is the fused clip+mask kernel's own stream
    family over global packed indices (what the barrier and wire tiers
    emit), on every platform and through either inner kernel."""
    from repro.kernels import REGISTRY

    n, sigma_c, b = 3, 2.0, 8.0
    t = {"w": jnp.zeros((40, 50), jnp.float32),
         "m": jnp.zeros((2, 7, 9), jnp.float32)}
    lay = flatbuf.layout_of(t)
    for i in range(n):
        with REGISTRY.record() as used:
            m = masking.pairwise_mask_tree(t, KEY_R, KEY_XI, jnp.int32(i), n,
                                           sigma_c, b, impl=impl)
        assert used == {"dp_fused_clip_mask": {impl}}
        expect = flatbuf.unpack(lay, fref.clip_mask_ref(
            jnp.zeros((lay.total,), jnp.float32), 1.0, KEY_R, KEY_XI, KEY_XI,
            jnp.int32(i), n, sigma_c, b, 0.0, use_prev=False))
        for a, e in zip(jax.tree.leaves(m), jax.tree.leaves(expect)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       atol=1e-5)


def test_packed_aggregate_noise_scale():
    n, sigma_c = 8, 3.0
    big = {"w": jnp.zeros((16384,), jnp.float32)}
    total = None
    for i in range(n):
        m = masking.pairwise_mask_tree(big, KEY_R, KEY_XI, jnp.int32(i), n,
                                       sigma_c, 8.0)
        total = m if total is None else jax.tree.map(jnp.add, total, m)
    std = float(np.std(np.asarray(total["w"])))
    assert abs(std - sigma_c) / sigma_c < 0.08


def test_barrier_sync_matches_manual_packed_construction():
    """clip+mask+correction fused dispatch == scale*g + packed mask - lam*prev
    computed leaf-free by hand (single silo axis psum elided)."""
    priv = PrivacyConfig(enabled=True, sigma=0.5, clip_bound=1.0,
                         noise_lambda=0.7, mask_scale=8.0)
    t = mixed_tree(jax.random.PRNGKey(9))
    lay = flatbuf.layout_of(t)
    packed = flatbuf.pack(lay, t)
    scale = jnp.float32(0.4)
    sigma_c = priv.sigma * 1.0
    out = fops.clip_mask_packed(packed, scale, KEY_R, KEY_XI, KEY_P,
                                jnp.int32(1), 4, sigma_c,
                                priv.mask_scale * sigma_c,
                                jnp.float32(priv.noise_lambda))
    expect = fref.clip_mask_ref(packed, scale, KEY_R, KEY_XI, KEY_P,
                                jnp.int32(1), 4, sigma_c,
                                priv.mask_scale * sigma_c,
                                jnp.float32(priv.noise_lambda))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-6)


def test_fused_noise_packed_first_step_has_no_correction():
    priv = PrivacyConfig(enabled=True, sigma=1.0, clip_bound=1.0,
                         noise_lambda=0.7)
    keys = barrier_mod.BarrierKeys(KEY_R, KEY_XI, KEY_P)
    t = {"w": jnp.zeros((2048,), jnp.float32)}
    fresh = init_state(jax.random.PRNGKey(0))  # has_prev=False
    lay = flatbuf.layout_of(t)
    noisy, new_state = barrier_mod.fused_noise_packed(
        flatbuf.pack(lay, t), priv, keys, fresh, 1.0)
    # gate=0 -> plain xi_t at scale sigma*C, from the single packed stream
    expect = fref.clip_mask_ref(
        jnp.zeros((lay.total,), jnp.float32), 1.0, KEY_XI, KEY_XI, KEY_P,
        jnp.int32(0), 1, 1.0, 0.0, 0.0, use_pairwise=False, use_prev=False)
    np.testing.assert_allclose(np.asarray(noisy), np.asarray(expect),
                               atol=1e-6)
    assert bool(new_state.has_prev)
    np.testing.assert_array_equal(np.asarray(new_state.prev_key),
                                  np.asarray(KEY_XI))


def test_fused_noise_packed_regenerates_prev_from_key():
    """Carrying only prev_key regenerates exactly lam*xi_{t-1} on the packed
    path (the O(1)-state noise correction, paper §4.4)."""
    priv = PrivacyConfig(enabled=True, sigma=2.0, clip_bound=1.0,
                         noise_lambda=0.7)
    g = jnp.zeros((4096,), jnp.float32)
    k1 = barrier_mod.BarrierKeys(KEY_R, KEY_XI, KEY_P)
    k2 = barrier_mod.BarrierKeys(KEY_R, KEY_P, KEY_XI)  # step-2 noise key
    s0 = init_state(jax.random.PRNGKey(0))
    xi1, s1 = barrier_mod.fused_noise_packed(g, priv, k1, s0, 1.0)
    n2, _ = barrier_mod.fused_noise_packed(g, priv, k2, s1, 1.0)
    lam0 = PrivacyConfig(enabled=True, sigma=2.0, clip_bound=1.0,
                         noise_lambda=0.0)
    xi2, _ = barrier_mod.fused_noise_packed(g, lam0, k2, s0, 1.0)
    expect = np.asarray(xi2) - 0.7 * np.asarray(xi1)
    np.testing.assert_allclose(np.asarray(n2), expect, atol=1e-5)


# ---------------------------------------------------------------------------
# hot-path integration: packed engine inside jit/vmap


def test_per_example_clipped_grad_packed_matches_manual():
    from repro.core import clipping

    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)

    key = jax.random.PRNGKey(0)
    p = {"w": jax.random.normal(key, (4, 1))}
    batch = {"x": jax.random.normal(key, (8, 4)),
             "y": jax.random.normal(key, (8, 1))}
    C = 0.5
    summed, norms, _ = jax.jit(
        lambda pp, bb: clipping.per_example_clipped_grad(loss, pp, bb, C))(
            p, batch)
    manual = np.zeros((4, 1), np.float32)
    for i in range(8):
        ex = {k: v[i:i + 1] for k, v in batch.items()}
        g = jax.grad(loss)(p, ex)["w"]
        n = float(jnp.linalg.norm(g))
        manual += np.asarray(g) * min(1.0, C / n)
    np.testing.assert_allclose(np.asarray(summed["w"]), manual, rtol=1e-4)
    assert norms.shape == (8,)
