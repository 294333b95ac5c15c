"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Interpret mode on the CPU cannot see what the TPU compiler (Mosaic) refuses:
block shapes off the (8, 128) tiling, casts it has no lowering for, too much
VMEM. Each test lowers one kernel for a described (not attached) v5e chip at
qwen2.5-3b widths (d_head 128, 16 query / 2 kv heads) and at the packed
gradient size of the 2-layer, 37,984-vocabulary-row cut that
``chip_smoke.py`` trains, and checks that the compiled program holds the
kernel as a ``tpu_custom_call``. Nothing runs; a compile takes about a
second.

The topology is described inside a fixture and never at import: only one
process may load the TPU library at a time.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.qwen25_3b import CONFIG
from repro.core import flatbuf
from repro.models.registry import build_model

N_SILOS = 2
SLOTS, PAGE, PAGES_PER_SLOT = 8, 16, 36  # 8 requests, 576-token slots


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def packed_size():
    cfg = dataclasses.replace(CONFIG, n_layers=2, vocab_size=37984)
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    return flatbuf.layout_of(params).total


def _cases(P):
    """name -> (kernel call, argument shapes) at the chip_smoke widths."""
    from repro.kernels.dp_clip.dp_clip import clip_accumulate, \
        per_example_sumsq
    from repro.kernels.dp_fused.dp_fused import (clip_mask_pallas,
                                                 clip_sum_pallas,
                                                 noise_batch_pallas)
    from repro.kernels.paged_attention.paged_attention import (
        paged_attention_pallas, paged_reset_pallas, paged_rollback_pallas)
    from repro.kernels.zsmask.zsmask import zsmask_pallas

    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    key, sc, silo = ((2,), u32), ((), f32), ((), i32)
    D, Hq, Hkv = CONFIG.d_head, CONFIG.n_heads, CONFIG.n_kv_heads
    n_pages = SLOTS * PAGES_PER_SLOT
    layers_pool = ((2, n_pages, Hkv, PAGE, D), f32)
    tables, qs = ((SLOTS, PAGES_PER_SLOT), i32), ((SLOTS,), i32)

    def attention(C, Hq=Hq, Hkv=Hkv, D=D):
        pool = ((n_pages, Hkv, PAGE, D), f32)
        return (paged_attention_pallas,
                [((SLOTS, C, Hq, D), f32), pool, pool, tables, qs, qs])

    return {
        "clip_sum": (clip_sum_pallas, [((N_SILOS, P), f32), sc]),
        "clip_mask": (lambda g, s, kr, kx, kp, i, sig, b, lam:
                      clip_mask_pallas(g, s, kr, kx, kp, i, N_SILOS, sig, b,
                                       lam),
                      [((P,), f32), sc, key, key, key, silo, sc, sc, sc]),
        "noise_batch": (noise_batch_pallas,
                        [((P,), f32), key, key, ((N_SILOS,), f32),
                         ((N_SILOS,), f32), sc]),
        "zsmask": (lambda g, kr, kx, i, sig, b:
                   zsmask_pallas(g, kr, kx, i, N_SILOS, sig, b),
                   [((P,), f32), key, key, silo, sc, sc]),
        "clip_accumulate": (clip_accumulate,
                            [((N_SILOS, P), f32), ((N_SILOS,), f32)]),
        "per_example_sumsq": (per_example_sumsq, [((N_SILOS, P), f32)]),
        "paged_attention_decode": attention(1),
        "paged_attention_prefill_chunk": attention(128),
        # phi3-mini's MHA decode (32 heads of 96, the pool's 128 lanes) and
        # a Zamba2 shared block's 256-row chunk (32 heads of 224 in 256)
        "paged_attention_mha_decode": attention(1, 32, 32, 128),
        "paged_attention_mha256_prefill_chunk": attention(256, 32, 32, 256),
        "paged_reset": (paged_reset_pallas,
                        [layers_pool, layers_pool, ((PAGES_PER_SLOT,), i32)]),
        "paged_rollback": (paged_rollback_pallas,
                           [layers_pool, layers_pool, ((2,), i32),
                            ((2,), i32)]),
    }


@pytest.mark.parametrize("kernel", [
    "clip_sum", "clip_mask", "noise_batch", "zsmask", "clip_accumulate",
    "per_example_sumsq", "paged_attention_decode",
    "paged_attention_prefill_chunk", "paged_attention_mha_decode",
    "paged_attention_mha256_prefill_chunk", "paged_reset", "paged_rollback"])
def test_kernel_compiles_for_v5e(kernel, one_chip, packed_size):
    fn, shapes = _cases(packed_size)[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _large_moves(hlo: str, size: int) -> list[str]:
    """The copies and reshapes in an HLO text whose result holds at least
    ``size`` elements (a bitcast moves nothing and is not listed)."""
    moves = []
    for line in hlo.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* (copy|reshape)\(", line)
        if m and math.prod(int(d) for d in m.group(1).split(",") if d) \
                >= size:
            moves.append(line.strip()[:160])
    return moves


@pytest.mark.parametrize("kernel", ["noise_batch", "clip_accumulate"])
def test_packed_kernel_streams_buffer_in_place(kernel, one_chip, packed_size):
    """At the real packed size the DP kernels' views of the buffer are free:
    one custom call, and no copy or relayout reshape of a P-sized array
    (the (P // 128, 128) view and the (1, P) -> (P,) hand-off lower to
    bitcasts)."""
    fn, shapes = _cases(packed_size)[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert _large_moves(hlo, packed_size) == []


@pytest.mark.parametrize("chunk", [256, 1])
def test_hybrid_paged_step_compiles_for_v5e(chunk, one_chip, monkeypatch):
    """The hybrid family's serving step at Zamba2-7B's published widths, one
    mamba and one hybrid layer, 8 slots of 4,096 tokens: a 256-row prefill
    chunk runs the SSD scan as the Pallas kernel (2 groups by its index map)
    and the shared block's attention as the paged kernel, both
    ``tpu_custom_call``s; decode runs the paged kernel and the one-step state
    update. The registry's resolutions show no fallback from Pallas."""
    from repro.configs.zamba2_7b import CONFIG as ZAMBA2
    from repro.kernels import dispatch
    from repro.kernels.mamba2 import ops as ssd_ops
    from repro.kernels.paged_attention import ops as paged_ops

    for mod in (dispatch, ssd_ops, paged_ops):  # the host is not the chip
        monkeypatch.setattr(mod, "on_tpu", lambda: True)
    cfg = dataclasses.replace(ZAMBA2, n_layers=2, hybrid_layer_ids=(1,))
    model = build_model(cfg, compute_dtype=jnp.float32)
    slots, max_len, page = 8, 4096, 16
    sds = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)
    params = jax.tree.map(sds, jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)))
    pool = jax.tree.map(sds, jax.eval_shape(
        lambda: model.init_paged_cache(slots * max_len // page, page,
                                       n_slots=slots)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    with dispatch.REGISTRY.record() as used:
        hlo = jax.jit(model.paged_step, donate_argnums=(2,)).lower(
            params, i32(slots, chunk), pool, i32(slots, max_len // page),
            i32(slots), i32(slots)).compile().as_text()
    calls = [l for l in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert any("paged_attention" in l for l in calls)
    assert used["paged_attention"] == {"pallas"}
    if chunk > 1:
        assert any("ssd" in l for l in calls), calls
        assert used["mamba2_ssd"] == {"pallas"}
