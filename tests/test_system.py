"""System behaviour: the two implementation tiers of the privacy barrier
(SPMD fused path vs component wire protocol) agree, and the paper models
train through the barrier end to end."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (MeshConfig, OptimizerConfig, PrivacyConfig,
                                RunConfig, SHAPES)
from repro.configs.paper_models import CIFAR10_CNN6, MNIST_MLP3
from repro.data.synthetic import synthetic_cifar10, synthetic_mnist
from repro.distributed import steps as steps_mod
from repro.models.registry import Model
from repro.models.small import build_small_model


def as_model(sm):
    return Model(cfg=None, init=sm.init, loss=sm.loss, init_cache=None,
                 prefill=None, decode_step=None)


def test_fused_path_equals_manual_dp_sgd():
    """The fused path's aggregate == sum(clip(g_i)) + regenerated noise,
    exactly (the paper's DP-SGD aggregate)."""
    sm = build_small_model(MNIST_MLP3)
    model = as_model(sm)
    priv = PrivacyConfig(enabled=True, sigma=0.5, clip_bound=1.0,
                         clip_mode="per_silo", n_silos=4)
    rc = RunConfig(model=None, shape=SHAPES["train_4k"],
                   mesh=MeshConfig((1,), ("data",)), privacy=priv,
                   optimizer=OptimizerConfig(name="sgd", lr=0.0))
    train, _ = synthetic_mnist(n_train=128, n_test=16)
    batch = {"x": jnp.asarray(train.x[:32]), "y": jnp.asarray(train.y[:32])}
    state = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))

    from repro.core import barrier as barrier_mod, clipping
    keys = barrier_mod.step_keys(jax.random.PRNGKey(9), jnp.zeros((), jnp.int32))
    noisy, loss, norms, ns, bound = steps_mod._fused_grads(
        model, priv, state.params, batch, 4, keys, state.noise_state,
        jnp.float32(1.0), keys.key_clip)

    manual = None
    for i in range(4):
        sl = {k: v[i * 8:(i + 1) * 8] for k, v in batch.items()}
        g = jax.grad(model.loss)(state.params, sl)
        g, _ = clipping.clip_tree(g, 1.0)
        manual = g if manual is None else jax.tree.map(
            lambda a, b: a + b, manual, g)
    # the fused path draws the engine's per-silo noise streams (the same
    # construction the barrier/wire tiers psum); adding the exact stream sum
    # to the manual clipped sum must reproduce the aggregate
    noise = barrier_mod.aggregate_noise_from_streams(
        state.params, keys, 4, priv.sigma * 1.0)
    expect = jax.tree.map(
        lambda m, n: m.astype(jnp.float32) + n, manual, noise)
    for a, b in zip(jax.tree.leaves(noisy), jax.tree.leaves(expect)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_silo_scan_mode_matches_vmap_mode():
    """The memory-optimal silo-serial path computes the same clipped
    aggregate as the vmap path. The scan path draws per-leaf noise (it keeps
    the FSDP-sharded accumulator), so its output less that noise, drawn on
    the same keys, equals the vmap path's noiseless (sigma 0) output."""
    from repro.core.noise_correction import corrected_noise

    sm = build_small_model(MNIST_MLP3)
    model = as_model(sm)
    train, _ = synthetic_mnist(n_train=128, n_test=16)
    batch = {"x": jnp.asarray(train.x[:32]), "y": jnp.asarray(train.y[:32])}
    from repro.core import barrier as barrier_mod
    keys = barrier_mod.step_keys(jax.random.PRNGKey(9), jnp.zeros((), jnp.int32))
    outs = {}
    for mode, sigma in (("vmap", 0.0), ("scan", 0.25)):
        priv = PrivacyConfig(enabled=True, sigma=sigma, clip_bound=1.0,
                             clip_mode="per_silo", n_silos=4, silo_mode=mode)
        rc = RunConfig(model=None, shape=SHAPES["train_4k"],
                       mesh=MeshConfig((1,), ("data",)), privacy=priv,
                       optimizer=OptimizerConfig(name="sgd", lr=0.0))
        state = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))
        fn = steps_mod._fused_grads if mode == "vmap" else steps_mod._fused_grads_scan
        noisy, *_ = fn(model, priv, state.params, batch, 4, keys,
                       state.noise_state, jnp.float32(1.0), keys.key_clip)
        outs[mode] = noisy
    noise, _ = corrected_noise(outs["scan"], keys.key_xi, state.noise_state,
                               0.25 * 1.0, 0.0)
    clipped = jax.tree.map(lambda a, n: a - n, outs["scan"], noise)
    for a, b in zip(jax.tree.leaves(outs["vmap"]), jax.tree.leaves(clipped)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_cnn6_trains_under_barrier():
    sm = build_small_model(CIFAR10_CNN6)
    model = as_model(sm)
    rc = RunConfig(model=None, shape=SHAPES["train_4k"],
                   mesh=MeshConfig((1,), ("data",)),
                   privacy=PrivacyConfig(enabled=True, sigma=0.02,
                                         clip_bound=1.0, n_silos=4),
                   optimizer=OptimizerConfig(name="momentum", lr=0.1))
    train, test = synthetic_cifar10(n_train=256, n_test=128)
    state = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))
    step = jax.jit(steps_mod.build_train_step(model, rc))
    losses = []
    for i in range(20):
        idx = np.random.default_rng(i).integers(0, 256, 32)
        b = {"x": jnp.asarray(train.x[idx]), "y": jnp.asarray(train.y[idx])}
        state, m = step(state, b, jax.random.PRNGKey(5))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], (losses[0], losses[-1])


def test_privacy_off_mode():
    """§9: mechanisms individually disableable (confidentiality without DP)."""
    sm = build_small_model(MNIST_MLP3)
    model = as_model(sm)
    rc = RunConfig(model=None, shape=SHAPES["train_4k"],
                   mesh=MeshConfig((1,), ("data",)),
                   privacy=PrivacyConfig(enabled=False, n_silos=4),
                   optimizer=OptimizerConfig(name="sgd", lr=0.5))
    train, _ = synthetic_mnist(n_train=128, n_test=16)
    state = steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0))
    step = jax.jit(steps_mod.build_train_step(model, rc))
    b = {"x": jnp.asarray(train.x[:32]), "y": jnp.asarray(train.y[:32])}
    l0 = None
    for i in range(10):
        state, m = step(state, b, jax.random.PRNGKey(2))
        l0 = l0 or float(m["loss"])
    assert float(m["loss"]) < l0 * 0.5
