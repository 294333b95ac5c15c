"""Per-kernel Pallas (interpret=True) vs pure-jnp oracle sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need it; collection must not
from hypothesis import given, settings, strategies as st

from repro.kernels.dp_clip import ref as dref
from repro.kernels.dp_clip.dp_clip import (PACKED_BLOCK_D, accumulate_blocks,
                                          clip_accumulate, per_example_sumsq)
from repro.kernels.flash_attention import ref as fref
from repro.kernels.flash_attention.blocked import flash_attention_xla
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.rwkv6 import ref as rref
from repro.kernels.rwkv6.rwkv6 import wkv_pallas
from repro.kernels.zsmask import ref as zref
from repro.kernels.zsmask.zsmask import zsmask_pallas
from repro.kernels.zsmask.threefry import threefry2x32


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("B,Sq,Hq,Hkv,D,causal,dtype", [
    (1, 128, 4, 4, 32, True, jnp.float32),
    (2, 256, 8, 2, 64, True, jnp.float32),
    (2, 128, 4, 1, 32, False, jnp.float32),
    (1, 256, 4, 2, 64, True, jnp.bfloat16),
    (3, 384, 6, 2, 16, True, jnp.float32),
])
def test_flash_pallas_vs_ref(B, Sq, Hq, Hkv, D, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sq, Hkv, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sq, Hkv, D)).astype(dtype)
    o_pal = flash_attention_pallas(q, k, v, causal=causal, block_q=128,
                                   block_k=128, interpret=True)
    o_ref = fref.attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol, rtol=tol)


def test_flash_xla_custom_vjp_grads():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 32))
    k = jax.random.normal(ks[1], (2, 256, 2, 32))
    v = jax.random.normal(ks[2], (2, 256, 2, 32))
    for causal in (True, False):
        g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(
            flash_attention_xla(*a, causal, 64))), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(
            fref.attention_ref(*a, causal))), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


# ---------------------------------------------------------------------------
# rwkv6 wkv


@pytest.mark.parametrize("B,S,H,N,chunk", [
    (1, 32, 2, 8, 16), (2, 64, 3, 16, 16), (2, 128, 2, 32, 32),
])
def test_rwkv_pallas_vs_sequential(B, S, H, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (B, S, H, N)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, N)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, N)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, N))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, N)) * 0.3
    s0 = jax.random.normal(ks[0], (B, H, N, N)) * 0.1
    o_seq, st_seq = rref.wkv_sequential(r, k, v, w, u, s0)
    o_chk, st_chk = rref.wkv_chunked_jnp(r, k, v, w, u, s0, chunk=chunk)
    o_pal, st_pal = wkv_pallas(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(o_chk), np.asarray(o_seq), atol=2e-5)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_seq), atol=2e-5)
    np.testing.assert_allclose(np.asarray(st_pal), np.asarray(st_seq), atol=2e-5)


def test_rwkv_strong_decay_stability():
    """Strong data-dependent decay (w near 0) must not overflow the chunked
    formulation (ratios stay <= 1)."""
    B, S, H, N = 1, 64, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    r = jax.random.normal(ks[0], (B, S, H, N))
    k = jax.random.normal(ks[1], (B, S, H, N))
    v = jax.random.normal(ks[2], (B, S, H, N))
    w = jnp.full((B, S, H, N), 0.05)  # aggressive decay
    u = jnp.zeros((H, N))
    s0 = jnp.zeros((B, H, N, N))
    o_seq, _ = rref.wkv_sequential(r, k, v, w, u, s0)
    o_pal, _ = wkv_pallas(r, k, v, w, u, s0, chunk=16, interpret=True)
    assert np.isfinite(np.asarray(o_pal)).all()
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_seq), atol=1e-4)


# ---------------------------------------------------------------------------
# dp_clip


@settings(deadline=None, max_examples=12)
@given(st.sampled_from([(8, 512), (16, 1024), (32, 2048), (8, 4096)]),
       st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_dp_clip_sweep(shape, dtype):
    B, D = shape
    g = (jax.random.normal(jax.random.PRNGKey(B + D), (B, D)) * 0.3).astype(dtype)
    s = jax.random.uniform(jax.random.PRNGKey(1), (B,))
    ss_pal = per_example_sumsq(g, interpret=True)
    ss_ref = dref.per_example_sumsq_ref(g)
    np.testing.assert_allclose(np.asarray(ss_pal), np.asarray(ss_ref),
                               rtol=3e-3)
    ca_pal = clip_accumulate(g, s, interpret=True)
    ca_ref = dref.clip_accumulate_ref(g, s)
    np.testing.assert_allclose(np.asarray(ca_pal), np.asarray(ca_ref),
                               rtol=3e-3, atol=1e-3)


@pytest.mark.parametrize("B,D,block_d", [
    (2, 1024 * 11, 4096),           # 3 blocks, the last of 3,072 lanes
    (4, 1024 * 5, 2048),            # 3 blocks, the last of 1,024 lanes
    (2, PACKED_BLOCK_D + 3072, None),  # the default wide block, ragged
    (4, 1024 * 7, None),            # the default: one block of every lane
])
def test_clip_accumulate_packed_wide_block_ragged(B, D, block_d):
    """A packed (n_silos, P) stack in lane-wide blocks of all B rows, the
    last block ragged: the sum equals the oracle's."""
    g = jax.random.normal(jax.random.PRNGKey(B + D), (B, D))
    s = jax.random.uniform(jax.random.PRNGKey(1), (B,))
    got = clip_accumulate(g, s, block_d=block_d, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(dref.clip_accumulate_ref(g, s)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("B,D,blocks", [
    (2, 231947264, (2, PACKED_BLOCK_D)),  # the 2-silo packed stack
    (8, 1024 * 3, (8, 1024 * 3)),         # 8 silos, a short buffer
    (8, 300, (8, 300)),                   # per-leaf: one small leaf
    (4, 1536, (4, 512)),                  # per-leaf: D not a multiple of 1024
    (16, 2048, (8, 512)),                 # per-leaf: more than 8 examples
])
def test_clip_accumulate_blocks_by_shape(B, D, blocks):
    """Only a packed stack (B <= 8, D a multiple of 1024) takes the wide
    block; a per-leaf (B, D) operand keeps the (min(8, B), 512) blocks."""
    assert accumulate_blocks(B, D) == blocks


# ---------------------------------------------------------------------------
# zsmask


def test_zsmask_pallas_bit_matches_ref_any_blocking():
    key_r = jnp.array([123, 456], jnp.uint32)
    key_xi = jnp.array([789, 12], jnp.uint32)
    D, n = 4096, 8
    g = jax.random.normal(jax.random.PRNGKey(0), (D,))
    ref_out = zref.zsmask_ref(g, key_r, key_xi, 3, n, 2.0, 8.0)
    for block in (512, 1024, 4096):
        pal = zsmask_pallas(g, key_r, key_xi, jnp.int32(3), n, 2.0, 8.0,
                            block_d=block, interpret=True)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref_out),
                                   atol=1e-5)


def test_threefry_reference_vector():
    """Known-answer test: threefry2x32 with zero key/counter (Random123
    reference vectors)."""
    x0, x1 = threefry2x32(jnp.uint32(0), jnp.uint32(0),
                          jnp.zeros((1,), jnp.uint32), jnp.zeros((1,), jnp.uint32))
    assert (int(x0[0]), int(x1[0])) == (0x6B200159, 0x99BA4EFE)


def test_uniform01_range_and_resolution():
    """The bitcast construction maps the top 23 bits onto a 2^-23 grid in
    (0, 1]: never 0 (the Box-Muller log), 1.0 at the bottom of the range."""
    from repro.kernels.zsmask.threefry import uniform01
    bits = jnp.asarray([0, 0x1FF, 0x200, 0x80000000, 0xFFFFFFFF], jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(uniform01(bits)),
        np.asarray([1.0, 1.0, 1.0 - 2.0 ** -23, 0.5, 2.0 ** -23], np.float32))


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 2**31 - 1), st.integers(2, 9))
def test_zsmask_gaussianity(seed, n):
    key_r = jnp.array([seed, seed ^ 0xABCDEF], jnp.uint32)
    key_xi = jnp.array([seed ^ 0x123, 7], jnp.uint32)
    m = zref.mask_only_ref(8192, key_r, key_xi, 0, n, 1.0, 0.0)
    z = np.asarray(m) * np.sqrt(n)  # back to unit normal
    assert abs(z.mean()) < 0.05
    assert abs(z.std() - 1.0) < 0.05
    assert abs((z < 0).mean() - 0.5) < 0.03


# ---------------------------------------------------------------------------
# mamba2 SSD


@pytest.mark.parametrize("B,S,nh,P,N,chunk", [
    (1, 64, 2, 8, 8, 16), (2, 128, 3, 16, 16, 32), (1, 96, 2, 32, 16, 32),
])
def test_mamba2_ssd_pallas_vs_sequential(B, S, nh, P, N, chunk):
    from repro.kernels.mamba2 import ref as mref
    from repro.kernels.mamba2.mamba2 import ssd_pallas
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    xh = jax.random.normal(ks[0], (B, S, nh, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    la = -jnp.abs(jax.random.normal(ks[2], (B, S, nh))) * 0.5  # log decay < 0
    Bc = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cc = jax.random.normal(ks[4], (B, S, N)) * 0.5
    h0 = jax.random.normal(ks[0], (B, nh, P, N)) * 0.1
    y_seq, h_seq = mref.ssd_sequential(xh, dt, la, Bc, Cc, h0)
    y_chk, h_chk = mref.ssd_chunked_jnp(xh, dt, la, Bc, Cc, h0, chunk=chunk)
    y_pal, h_pal = ssd_pallas(xh, dt, la, Bc, Cc, h0, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_seq), atol=5e-5)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_seq), atol=5e-5)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_seq), atol=5e-5)
