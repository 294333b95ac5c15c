"""Microbenchmarks of the kernel ops across every registered impl.

Uses the dispatch registry's introspection (``available_impls``) to sweep
each kernel's variants under identical inputs, so a newly registered impl
shows up here with zero benchmark changes. Pallas variants run in interpret
mode on CPU (correctness-path overhead, not TPU speed; the roofline table
covers TPU projections) and are skipped off-TPU by default — set
``BENCH_ALL_IMPLS=1`` to include them.

The ``dp_tree`` section times the packed flat-buffer DP engine (O(1)
dispatches over one flat buffer) across leaf counts.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit, timeit_interleaved
from repro.core import masking
from repro.kernels import available_impls
from repro.kernels.dp_clip import ops as dops
from repro.kernels.flash_attention import ops as fops
from repro.kernels.mamba2 import ops as mops
from repro.kernels.rwkv6 import ops as rops
from repro.kernels.zsmask import ops as zops


def _impls(kernel: str, include_pallas: bool) -> list[str]:
    return [n for n in available_impls(kernel)
            if include_pallas or n != "pallas"]


def _synthetic_tree(key, n_leaves: int, B: int, elem: int) -> dict:
    """Per-example gradient pytree with ``n_leaves`` leaves of slightly
    varied, deliberately lane-unaligned sizes."""
    ks = jax.random.split(key, n_leaves)
    return {f"w{i}": jax.random.normal(ks[i], (B, elem + 32 * (i % 3) + 1))
            for i in range(n_leaves)}


def run():
    include_pallas = bool(int(os.environ.get("BENCH_ALL_IMPLS", "0"))) \
        or jax.default_backend() == "tpu"
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)

    # flash attention, train-ish shape
    B, S, Hq, Hkv, D = 2, 1024, 8, 2, 64
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    for impl in _impls("flash_attention", include_pallas):
        f = jax.jit(lambda a, b, c, i=impl: fops.flash_attention(a, b, c, True,
                                                                 impl=i))
        emit(f"kernels/attention_{impl}_s{S}", timeit(f, q, k, v),
             impl=impl, shape=f"B={B},S={S},Hq={Hq},Hkv={Hkv},D={D}")

    # rwkv6 wkv
    B, S, H, N = 2, 512, 4, 32
    r = jax.random.normal(ks[0], (B, S, H, N)) * 0.5
    kk = jax.random.normal(ks[1], (B, S, H, N)) * 0.5
    vv = jax.random.normal(ks[2], (B, S, H, N)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, N))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, N)) * 0.3
    s0 = jnp.zeros((B, H, N, N))
    for impl in _impls("rwkv6_wkv", include_pallas):
        f = jax.jit(lambda *a, i=impl: rops.wkv_chunked(*a, impl=i)[0])
        emit(f"kernels/rwkv_{impl}_s{S}", timeit(f, r, kk, vv, w, u, s0),
             impl=impl, shape=f"B={B},S={S},H={H},N={N}")

    # mamba2 ssd
    B, S, nh, P, N = 2, 512, 4, 32, 32
    xh = jax.random.normal(ks[0], (B, S, nh, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    la = -jnp.abs(jax.random.normal(ks[2], (B, S, nh))) * 0.5
    Bc = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cc = jax.random.normal(ks[4], (B, S, N)) * 0.5
    h0 = jnp.zeros((B, nh, P, N))
    for impl in _impls("mamba2_ssd", include_pallas):
        f = jax.jit(lambda *a, i=impl: mops.ssd_chunked(*a, impl=i)[0])
        emit(f"kernels/mamba2_{impl}_s{S}", timeit(f, xh, dt, la, Bc, Cc, h0),
             impl=impl, shape=f"B={B},S={S},nh={nh},P={P},N={N}")

    # dp_clip fused vs two-pass (single block)
    g = jax.random.normal(ks[0], (256, 8192))
    for impl in _impls("dp_clip_sumsq", include_pallas):
        f = jax.jit(lambda a, i=impl: dops.sumsq(a, impl=i))
        emit(f"kernels/dp_sumsq_{impl}_256x8192", timeit(f, g),
             impl=impl, shape="B=256,D=8192")

    # zsmask (single flat buffer)
    gflat = jax.random.normal(ks[0], (1 << 20,))
    kr = jnp.array([123, 456], jnp.uint32)
    kx = jnp.array([789, 12], jnp.uint32)
    for impl in _impls("zsmask", include_pallas):
        f = jax.jit(lambda a, i=impl: zops.apply_zsmask(
            a, kr, kx, 0, 4, 1.0, 8.0, impl=i))
        emit(f"kernels/zsmask_{impl}_1m", timeit(f, gflat),
             impl=impl, shape="D=1048576")

    # the packed flat-buffer engine across leaf counts: the DP hot path on
    # synthetic gradient pytrees. dp_tree isolates the clip+sum op;
    # mask_tree isolates the mask; dp_pipeline is the full per-step
    # clip+sum+corrected-noise composition as the step builders run it
    # (packed stays packed between the ops, so the pack/unpack cost is paid
    # once per step, not once per op).
    from repro.core import barrier as barrier_mod, flatbuf
    from repro.kernels.dp_fused import ops as fused_ops
    from repro.core.noise_correction import NoiseState
    from repro.configs.base import PrivacyConfig

    priv = PrivacyConfig(enabled=True, sigma=0.5, clip_bound=1.0,
                         noise_lambda=0.7)
    keys = barrier_mod.step_keys(jax.random.PRNGKey(1), jnp.zeros((), jnp.int32))
    nstate = NoiseState(prev_key=jnp.array([9, 9], jnp.uint32),
                        has_prev=jnp.ones((), jnp.bool_))
    B = 8
    for n_leaves in (8, 64, 256):
        tree = _synthetic_tree(ks[3], n_leaves, B, 64)
        shape = f"leaves={n_leaves},B={B}"
        f = jax.jit(lambda t: fused_ops.packed_clip_and_sum(t, 1.0)[0])
        emit(f"kernels/dp_tree_packed_l{n_leaves}", timeit(f, tree),
             impl="packed", shape=shape)
        elem_tree = {k: v[0] for k, v in tree.items()}
        f = jax.jit(lambda t: masking.pairwise_mask_tree(
            t, kr, kx, 0, 4, 1.0, 8.0))
        emit(f"kernels/mask_tree_packed_l{n_leaves}",
             timeit(f, elem_tree), impl="packed", shape=shape)

        # the dp_pipeline rows run one (n, P) buffer through the two
        # central-tier constructions at the repo's canonical 4-silo
        # collaboration size (every paper config and test pairs 4 dataset
        # owners): ``packed`` is the fixed-membership clip+sum+2-stream
        # aggregate-noise composition, ``active_*`` is the elastic engine
        # (per-silo sigma_c/sqrt(k) streams, ring masks, participation
        # gating). active_set pays the dynamic-membership graph; an
        # all-active set known at trace time takes the static fast path,
        # whose only remaining cost over ``packed`` is the per-silo noise
        # streams the cross-tier bit-parity contract requires — CI gates
        # that overhead at 1.25x (see ``check``). The three rows are
        # measured interleaved: they compare close variants of one graph,
        # and host scheduling noise between separate timeit calls would
        # dwarf the effect.
        from repro.core.dp_pipeline import DPPipeline

        n_silos = 4
        silo_tree = {k: v[:n_silos] for k, v in tree.items()}
        silo_layout = flatbuf.layout_of({k: v[0] for k, v in tree.items()})
        batch_layout = flatbuf.layout_of(silo_tree, batch_dims=1)
        pipe = DPPipeline(priv, silo_layout, n_silos)
        active_drop = jnp.ones((n_silos,), jnp.bool_).at[1].set(False)
        active_full = jnp.ones((n_silos,), jnp.bool_)
        pshape = f"leaves={n_leaves},n={n_silos}"

        def pipeline_packed(t):
            summed, norms = fused_ops.clip_sum_packed(
                flatbuf.pack(batch_layout, t), 1.0)
            noisy, _ = barrier_mod.fused_noise_packed(summed, priv, keys,
                                                      nstate, 1.0)
            return flatbuf.unpack(batch_layout, noisy, dtype=jnp.float32)

        def pipeline_active(t, active):
            # batch-pack rows are bitwise-equal to per-silo packs, minus
            # the vmap dispatch overhead
            stacked = flatbuf.pack(batch_layout, t)  # (n, P)
            noisy, _, _ = pipe.run_central(
                stacked, pipe.norms(stacked), keys, nstate, 1.0,
                keys.key_clip, active)
            return noisy

        us = timeit_interleaved([
            (jax.jit(pipeline_packed), (silo_tree,)),
            (jax.jit(pipeline_active), (silo_tree, active_drop)),
            (jax.jit(lambda t: pipeline_active(t, active_full)), (silo_tree,)),
        ])
        emit(f"kernels/dp_pipeline_packed_l{n_leaves}", us[0],
             impl="packed", shape=pshape)
        emit(f"kernels/dp_pipeline_active_set_l{n_leaves}", us[1],
             impl="packed", shape=pshape + f",k={n_silos - 1}/{n_silos}")
        emit(f"kernels/dp_pipeline_active_static_l{n_leaves}", us[2],
             impl="packed", shape=pshape + f",k={n_silos}/{n_silos} (static)")


def check(json_path: str = "BENCH_kernels.json",
          max_ratio: float = 1.25) -> None:
    """CI gate on the elastic engine's hot path: the statically-full
    participation set must stay within ``max_ratio`` of the fixed-membership
    packed pipeline at the largest leaf count. The static fast path elides
    every piece of elastic bookkeeping, so the only cost it is allowed to
    keep over ``packed`` is the per-silo noise streams the cross-tier
    bit-parity contract requires — all generated by the one-launch
    ``noise_batch`` kernel. A regression here means either the batched noise
    kernel stopped being one dispatch or the static path regrew dynamic-set
    work."""
    import json

    with open(json_path) as f:
        rows = json.load(f)
    packed = rows["kernels/dp_pipeline_packed_l256"]["us_per_call"]
    static = rows["kernels/dp_pipeline_active_static_l256"]["us_per_call"]
    ratio = static / packed
    line = (f"check: active_static_l256={static:.1f}us "
            f"packed_l256={packed:.1f}us ratio={ratio:.3f} "
            f"(gate {max_ratio:.2f}x)")
    print(line)
    if ratio > max_ratio:
        raise SystemExit(f"FAIL {line}")
    print("kernels-bench check OK")


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="gate dp_pipeline_active_static_l256 <= 1.25x "
                         "dp_pipeline_packed_l256 from the written JSON "
                         "(runs the benchmarks first if the file is absent)")
    ap.add_argument("--json", default="BENCH_kernels.json",
                    help="records file to check / write")
    args = ap.parse_args()
    if not args.check:
        run()
        return
    if not os.path.exists(args.json):
        from benchmarks.common import write_json
        run()
        write_json(args.json)
    check(args.json)


if __name__ == "__main__":
    main()
