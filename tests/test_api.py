"""repro.api Session façade: construction, train/serve wiring, arch-id
normalization, and metrics-log persistence across trainer restarts."""
import jax
import numpy as np
import pytest

from repro.api import ServeResult, Session, TrainResult
from repro.configs import resolve_arch
from repro.configs.base import MeshConfig, OptimizerConfig, PrivacyConfig


def _session(**kw):
    kw.setdefault("privacy", PrivacyConfig(enabled=True, sigma=0.5, n_silos=4))
    kw.setdefault("optimizer", OptimizerConfig(lr=1e-3))
    return Session.from_config("qwen2.5-3b", **kw)


def test_resolve_arch_accepts_all_spellings():
    for spelling in ("qwen2.5-3b", "qwen25_3b", "QWEN2.5-3B", "qwen2_5_3b"):
        assert resolve_arch(spelling) == "qwen2.5-3b"
    assert resolve_arch("rwkv6_7b") == "rwkv6-7b"
    assert resolve_arch("phi35_moe_42b") == "phi3.5-moe-42b-a6.6b"
    with pytest.raises(KeyError):
        resolve_arch("gpt-17")


def test_session_train_produces_metrics_and_updates_params():
    sess = _session()
    state0 = sess.init_state()
    # the jitted step donates the state, so snapshot before training
    params0 = [np.asarray(p) for p in jax.tree.leaves(state0.params)]
    res = sess.train(steps=2, batch_size=4, seq_len=32, log_every=0,
                     state=state0)
    assert isinstance(res, TrainResult)
    assert res.step == 2
    assert len(res.metrics) == 2
    assert {"loss", "epsilon", "step_time_s"} <= set(res.final)
    # params actually moved
    diffs = [float(np.abs(a - np.asarray(b)).max()) for a, b in
             zip(params0, jax.tree.leaves(res.state.params))]
    assert max(diffs) > 0


def test_session_serve_greedy_decode_shapes():
    sess = _session()
    res = sess.serve(batch_size=2, prompt_len=8, max_new_tokens=3)
    assert isinstance(res, ServeResult)
    assert res.tokens.shape == (2, 3)
    assert res.tokens.dtype.kind == "i"
    assert (res.tokens >= 0).all() and (res.tokens < sess.cfg.vocab_size).all()


def test_session_serve_accepts_external_params():
    sess = _session()
    params = sess.model.init(jax.random.PRNGKey(7))
    r1 = sess.serve(batch_size=1, prompt_len=8, max_new_tokens=2, params=params)
    r2 = sess.serve(batch_size=1, prompt_len=8, max_new_tokens=2, params=params)
    np.testing.assert_array_equal(r1.tokens, r2.tokens)  # deterministic


def test_session_defaults_privacy_off():
    sess = Session.from_config("qwen25_3b")
    assert not sess.run_cfg.privacy.enabled
    assert sess.run_cfg.mesh == MeshConfig((jax.device_count(),), ("data",))


def test_kernel_impls_introspection():
    impls = _session().kernel_impls()
    assert "flash_attention" in impls
    assert "pallas" in impls["flash_attention"]


def test_trainer_metrics_log_survives_restart(tmp_path):
    """Preemption bugfix: metrics history must restore from the checkpoint."""
    ckpt = str(tmp_path / "ckpt")
    sess = _session()
    res1 = sess.train(steps=2, batch_size=4, seq_len=32, log_every=0,
                      checkpoint_dir=ckpt, checkpoint_every=1)
    assert len(res1.metrics) == 2
    # fresh trainer restores from step 2 and keeps the earlier history
    res2 = sess.train(steps=4, batch_size=4, seq_len=32, log_every=0,
                      checkpoint_dir=ckpt, checkpoint_every=1)
    assert res2.step == 4
    steps_seen = [m["step"] for m in res2.metrics]
    assert steps_seen == [0, 1, 2, 3]  # old history + resumed steps, no gap


def test_compile_cache_env_wins_else_fixed_checkout_path(monkeypatch):
    """The entry points' cache helper: JAX_COMPILATION_CACHE_DIR, when set,
    is left to JAX; otherwise the cache goes to one fixed in-checkout path."""
    from pathlib import Path

    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == jax.config.jax_compilation_cache_dir
        assert Path(path) == Path(__file__).resolve().parents[1] / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
