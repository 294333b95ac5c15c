"""Kernel-dispatch registry: registration, selection policy, overrides, and
legacy impl-name compatibility across all five kernel packages."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import REGISTRY, available_impls, force_impl
from repro.kernels.dispatch import ENV_VAR, KernelRegistry
from repro.kernels.dp_clip import ops as dops
from repro.kernels.flash_attention import ops as fops
from repro.kernels.mamba2 import ops as mops
from repro.kernels.rwkv6 import ops as rops
from repro.kernels.zsmask import ops as zops


# ---------------------------------------------------------------------------
# registry mechanics (on a private registry, not the global one)


def _toy_registry():
    reg = KernelRegistry()

    @reg.register("k", "fast", priority=100,
                  predicate=lambda ctx: ctx["n"] % 4 == 0,
                  auto_predicate=lambda ctx: ctx["on_tpu"])
    def fast(x):
        return ("fast", x)

    @reg.register("k", "mid", priority=50,
                  auto_predicate=lambda ctx: ctx["n"] >= 100)
    def mid(x):
        return ("mid", x)

    @reg.register("k", "ref", priority=10)
    def ref(x):
        return ("ref", x)

    return reg


def test_registration_and_priority_order():
    reg = _toy_registry()
    assert reg.kernels() == ["k"]
    assert reg.available_impls("k") == ["fast", "mid", "ref"]
    with pytest.raises(ValueError, match="already registered"):
        reg.register("k", "ref")(lambda x: x)
    with pytest.raises(KeyError):
        reg.available_impls("nope")


def test_auto_selection_respects_preferences():
    reg = _toy_registry()
    # off-TPU, small n: fast not preferred, mid not preferred -> ref
    assert reg.resolve("k", "auto", {"n": 8, "on_tpu": False}).name == "ref"
    # off-TPU, large n: mid preferred
    assert reg.resolve("k", "auto", {"n": 128, "on_tpu": False}).name == "mid"
    # "TPU": fast preferred and capable
    assert reg.resolve("k", "auto", {"n": 8, "on_tpu": True}).name == "fast"
    # "TPU" but incapable (n % 4 != 0): falls past fast to ref
    assert reg.resolve("k", "auto", {"n": 7, "on_tpu": True}).name == "ref"


def test_explicit_request_bypasses_preference_but_not_capability():
    reg = _toy_registry()
    # mid never auto-selected for small n, but explicit request wins
    assert reg.resolve("k", "mid", {"n": 8, "on_tpu": False}).name == "mid"
    # explicit fast with a non-divisible n is rejected by the capability
    # predicate and falls back to the best remaining variant
    assert reg.resolve("k", "fast", {"n": 7, "on_tpu": False}).name == "ref"
    with pytest.raises(ValueError, match="unknown impl"):
        reg.resolve("k", "nope", {"n": 8, "on_tpu": False})


def test_force_impl_context_manager_scoping_and_nesting():
    reg = _toy_registry()
    ctx = {"n": 8, "on_tpu": False}
    with reg.force_impl("mid"):
        assert reg.resolve("k", "auto", ctx).name == "mid"
        with reg.force_impl("ref", "k"):  # innermost wins
            assert reg.resolve("k", "auto", ctx).name == "ref"
        assert reg.resolve("k", "auto", ctx).name == "mid"
    assert reg.resolve("k", "auto", ctx).name == "ref"  # stack unwound
    with reg.force_impl("mid", "other_kernel"):  # scoped elsewhere: no effect
        assert reg.resolve("k", "auto", ctx).name == "ref"


def test_env_var_override(monkeypatch):
    reg = _toy_registry()
    ctx = {"n": 8, "on_tpu": False}
    monkeypatch.setenv(ENV_VAR, "mid")  # bare name: every kernel
    assert reg.resolve("k", "auto", ctx).name == "mid"
    monkeypatch.setenv(ENV_VAR, "k=mid,other=ref")  # per-kernel list
    assert reg.resolve("k", "auto", ctx).name == "mid"
    monkeypatch.setenv(ENV_VAR, "other=mid")  # not for this kernel
    assert reg.resolve("k", "auto", ctx).name == "ref"
    # force_impl outranks the env var
    monkeypatch.setenv(ENV_VAR, "mid")
    with reg.force_impl("ref"):
        assert reg.resolve("k", "auto", ctx).name == "ref"


def test_global_override_with_foreign_impl_name_is_ignored(monkeypatch):
    """A fleet-wide override naming an impl some kernel doesn't have must not
    crash that kernel; a scoped override with a bad name must."""
    reg = _toy_registry()
    ctx = {"n": 8, "on_tpu": False}
    monkeypatch.setenv(ENV_VAR, "blocked")  # no such impl on kernel "k"
    assert reg.resolve("k", "auto", ctx).name == "ref"
    assert reg.resolve("k", "mid", ctx).name == "mid"  # call-site still wins
    monkeypatch.setenv(ENV_VAR, "k=blocked")  # scoped: explicit target, error
    with pytest.raises(ValueError, match="unknown impl"):
        reg.resolve("k", "auto", ctx)
    monkeypatch.delenv(ENV_VAR)
    with reg.force_impl("blocked"):  # global force: same tolerance
        assert reg.resolve("k", "auto", ctx).name == "ref"
    with reg.force_impl("blocked", "k"), pytest.raises(ValueError,
                                                      match="unknown impl"):
        reg.resolve("k", "auto", ctx)


def test_record_collects_resolved_variants():
    """record() sees what actually ran, fallbacks included, and only inside
    its block; nested records both see the inner resolutions."""
    reg = _toy_registry()
    with reg.record() as outer:
        reg.resolve("k", "auto", {"n": 8, "on_tpu": True})
        with reg.record() as inner:
            reg.resolve("k", "fast", {"n": 7, "on_tpu": True})  # incapable
    reg.resolve("k", "mid", {"n": 8, "on_tpu": False})
    assert outer == {"k": {"fast", "ref"}}
    assert inner == {"k": {"ref"}}


def test_dispatch_calls_selected_fn():
    reg = _toy_registry()
    assert reg.dispatch("k", "auto", {"n": 8, "on_tpu": False}, 42) == ("ref", 42)
    assert reg.dispatch("k", "mid", {"n": 8, "on_tpu": False}, 7) == ("mid", 7)


# ---------------------------------------------------------------------------
# the real kernel tables


EXPECTED_IMPLS = {
    "dp_clip_sumsq": {"pallas", "jnp"},
    "dp_clip_accumulate": {"pallas", "jnp"},
    "dp_clip_tree": {"packed", "perleaf", "pallas", "jnp"},
    "dp_fused_clip_sum": {"pallas", "jnp"},
    "dp_fused_clip_mask": {"pallas", "jnp"},
    "dp_fused_noise_batch": {"pallas", "jnp"},
    "dp_noise_tree": {"packed", "perleaf", "pallas", "jnp"},
    "flash_attention": {"pallas", "blocked", "blocked_naive", "jnp"},
    "mamba2_ssd": {"pallas", "jnp", "sequential"},
    "paged_attention": {"pallas", "gather", "jnp"},
    "paged_reset": {"pallas", "jnp"},
    "paged_rollback": {"pallas", "jnp"},
    "rwkv6_wkv": {"pallas", "jnp", "masked", "sequential"},
    "zsmask": {"pallas", "jnp"},
    "zsmask_tree": {"packed", "perleaf", "pallas", "jnp"},
}


def test_all_kernels_registered_with_legacy_impl_names():
    assert set(REGISTRY.kernels()) == set(EXPECTED_IMPLS)
    for kernel, names in EXPECTED_IMPLS.items():
        assert set(available_impls(kernel)) == names, kernel
        for name in names:  # every legacy impl string still resolves
            assert REGISTRY.get(kernel, name).name == name


def _flash_inputs(S=128):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, S, 4, 32))
    k = jax.random.normal(ks[1], (1, S, 2, 32))
    v = jax.random.normal(ks[2], (1, S, 2, 32))
    return q, k, v


def test_flash_every_impl_matches_reference():
    q, k, v = _flash_inputs()
    ref = fops.flash_attention(q, k, v, impl="jnp")
    for impl in EXPECTED_IMPLS["flash_attention"]:
        out = fops.flash_attention(q, k, v, impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, err_msg=impl)


def test_flash_auto_prefers_blocked_for_long_sequences():
    assert REGISTRY.resolve("flash_attention", "auto", {"S": 4096}).name \
        in ("blocked", "pallas")  # pallas only on TPU
    if jax.default_backend() != "tpu":
        assert REGISTRY.resolve("flash_attention", "auto", {"S": 4096}).name == "blocked"
        assert REGISTRY.resolve("flash_attention", "auto", {"S": 128}).name == "jnp"


def _rwkv_inputs(S):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (1, S, 2, 8)) * 0.3
    k = jax.random.normal(ks[1], (1, S, 2, 8)) * 0.3
    v = jax.random.normal(ks[2], (1, S, 2, 8)) * 0.3
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (1, S, 2, 8))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (2, 8)) * 0.3
    s0 = jnp.zeros((1, 2, 8, 8))
    return r, k, v, w, u, s0


def test_rwkv_nondivisible_seq_falls_back_from_pallas():
    # S=48 not divisible by chunk=32: explicit pallas request must fall back
    assert REGISTRY.resolve("rwkv6_wkv", "pallas",
                            {"S": 48, "chunk": 32}).name == "jnp"
    args = _rwkv_inputs(48)
    o_pal, _ = rops.wkv_chunked(*args, chunk=32, impl="pallas")
    o_seq, _ = rops.wkv_chunked(*args, impl="sequential")
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_seq), atol=2e-4)


def test_mamba2_nondivisible_seq_falls_back_from_pallas():
    assert REGISTRY.resolve("mamba2_ssd", "pallas",
                            {"S": 48, "chunk": 32}).name == "jnp"
    assert REGISTRY.resolve("mamba2_ssd", "pallas",
                            {"S": 64, "chunk": 32}).name == "pallas"


def test_zsmask_offset_falls_back_from_pallas():
    assert REGISTRY.resolve("zsmask", "pallas", {"offset": 5}).name == "jnp"
    assert REGISTRY.resolve("zsmask", "pallas", {"offset": 0}).name == "pallas"
    g = jax.random.normal(jax.random.PRNGKey(0), (512,))
    kr = jnp.array([1, 2], jnp.uint32)
    kx = jnp.array([3, 4], jnp.uint32)
    a = zops.apply_zsmask(g, kr, kx, 0, 4, 1.0, 4.0, impl="jnp")
    b = zops.apply_zsmask(g, kr, kx, 0, 4, 1.0, 4.0, impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_force_impl_reaches_kernel_call_sites():
    q, k, v = _flash_inputs(4096)  # auto would pick blocked on CPU
    with force_impl("jnp", "flash_attention"):
        assert REGISTRY.resolve("flash_attention", "auto", {"S": 4096}).name == "jnp"
    # global force applies to every kernel, including incapable explicit ones
    with force_impl("jnp"):
        assert REGISTRY.resolve("mamba2_ssd", "pallas",
                                {"S": 64, "chunk": 16}).name == "jnp"
        assert REGISTRY.resolve("zsmask", "auto", {"offset": 0}).name == "jnp"


def test_env_override_on_real_kernels(monkeypatch):
    monkeypatch.setenv(ENV_VAR, "flash_attention=blocked_naive")
    assert REGISTRY.resolve("flash_attention", "auto", {"S": 128}).name \
        == "blocked_naive"
    # other kernels unaffected
    assert REGISTRY.resolve("zsmask", "auto", {"offset": 0}).name \
        in ("jnp", "pallas")
    q, k, v = _flash_inputs()
    ref = fops.flash_attention(q, k, v, impl="jnp")
    np.testing.assert_allclose(np.asarray(fops.flash_attention(q, k, v)),
                               np.asarray(ref), atol=2e-5)


def test_dp_clip_tree_impls_agree():
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    tree = {"a": jax.random.normal(ks[0], (4, 3, 3)),
            "b": jax.random.normal(ks[1], (4, 7))}
    s_jnp, n_jnp = dops.clip_and_sum_tree(tree, 1.0, impl="jnp")
    s_pal, n_pal = dops.clip_and_sum_tree(tree, 1.0, impl="pallas")
    np.testing.assert_allclose(np.asarray(n_jnp), np.asarray(n_pal), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(s_jnp), jax.tree.leaves(s_pal)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_mamba2_every_impl_matches_sequential():
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    xh = jax.random.normal(ks[0], (1, 64, 2, 8)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 64, 2)))
    la = -jnp.abs(jax.random.normal(ks[2], (1, 64, 2))) * 0.5
    Bc = jax.random.normal(ks[3], (1, 64, 8)) * 0.5
    Cc = jax.random.normal(ks[4], (1, 64, 8)) * 0.5
    h0 = jnp.zeros((1, 2, 8, 8))
    y_ref, h_ref = mops.ssd_chunked(xh, dt, la, Bc, Cc, h0, impl="sequential")
    for impl in EXPECTED_IMPLS["mamba2_ssd"]:
        y, h = mops.ssd_chunked(xh, dt, la, Bc, Cc, h0, chunk=16, impl=impl)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=5e-5, err_msg=impl)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   atol=5e-5, err_msg=impl)


def test_rwkv_every_impl_matches_sequential():
    args = _rwkv_inputs(64)
    o_ref, s_ref = rops.wkv_chunked(*args, impl="sequential")
    for impl in EXPECTED_IMPLS["rwkv6_wkv"]:
        o, s = rops.wkv_chunked(*args, chunk=16, impl=impl)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=2e-4, err_msg=impl)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   atol=2e-4, err_msg=impl)
