"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell against
the production mesh, prove memory fits, and extract the roofline terms.

The XLA_FLAGS lines below MUST stay the first statements — jax locks the
device count on first init. Do not import this module from tests (they want 1
device); run it as ``python -m repro.launch.dryrun``.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out experiments/dryrun
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo_cost
from repro.configs import (SHAPES, get_config, runnable_cells, param_count,
                           active_param_count, shape_applicability)
from repro.configs.base import (MeshConfig, ModelConfig, OptimizerConfig,
                                PrivacyConfig, RunConfig, ShapeConfig)
from repro.distributed import steps as steps_mod
from repro.distributed.sharding_rules import (current_mesh, params_pspecs,
                                              spec_for)
from repro.launch import specs as specs_mod
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh, mesh_config)
from repro.models.registry import build_model


def _sds_sharding(tree_specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda s: isinstance(s, P))


def cache_pspecs(cache, mesh_cfg: MeshConfig):
    """Leaf-name-based specs for KV caches / recurrent states, with
    sequence-parallel fallback when batch=1 (long-context decode)."""
    silo = mesh_cfg.silo_axes
    silo_n = mesh_cfg.n_silos

    def leaf(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        nd = x.ndim
        if name in ("k", "v") and nd == 5:  # (E, B, S, H, D)
            # when kv_heads < TP, put the model axis on the cache's seq dim
            # instead (§Perf iteration 4: mistral decode cache was 284GB/dev
            # with only batch-sharding — kv=8 can't fill model=16)
            mesh = current_mesh()
            tp = mesh.shape.get("model", 1) if mesh is not None else 1
            seq_name = "seq_tp" if (x.shape[3] % max(tp, 1) != 0) else None
            if x.shape[1] % silo_n == 0 and x.shape[1] > 1:
                return spec_for((None, "batch", seq_name, "kv_heads", None), x.shape)
            return spec_for((None, None, "seq", "kv_heads", None), x.shape)
        if name == "S" and nd == 5:  # rwkv state (L,B,H,N,N)
            return spec_for((None, "batch", "heads", None, None), x.shape)
        if name == "h" and nd == 5:  # mamba state (L,B,nh,P,N)
            return spec_for((None, "batch", "heads", None, None), x.shape)
        if name == "conv" and nd == 4:
            return spec_for((None, "batch", None, None), x.shape)
        if name in ("x_prev", "x_prev_cm") and nd == 3:
            return spec_for((None, "batch", None), x.shape)
        return P()

    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    specs = [leaf(p, x) for p, x in flat]
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(cache), specs)


def build_cell(arch: str, shape_name: str, mesh_cfg: MeshConfig,
               sync_path: str = "fused", sequence_parallel: bool = True):
    """Returns (step_fn, example_inputs(SDS), in_shardings, out_shardings,
    donate, meta)."""
    cfg = get_config(arch)
    # SP only where residual memory is the feasibility blocker (>=50B dense);
    # on smaller models the partitioner's remat re-gathers outweigh the win
    # (§Perf iteration 3c, refuted on rwkv6: collective 10->23s)
    if sequence_parallel and cfg.family in ("dense", "vlm", "encoder") \
            and SHAPES[shape_name].kind == "train" and param_count(cfg) > 50e9:
        cfg = dataclasses.replace(cfg, sequence_parallel=True)
    shape = SHAPES[shape_name]
    model = build_model(cfg, param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
                        remat=True, use_flash=True)
    mesh = make_production_mesh(multi_pod=(len(mesh_cfg.shape) == 3))

    if shape.kind == "train":
        # Production train path: silo-serial (scan) with 8 data owners — the
        # per-silo grad transient reduce-scatters to P/n_devices, and the
        # silo serialization doubles as microbatching for activation memory
        # (DESIGN.md §6).
        priv = PrivacyConfig(enabled=True, sigma=1.0, clip_mode="per_silo",
                             sync_path=sync_path, silo_mode="scan", n_silos=8)
        rc = RunConfig(model=cfg, shape=shape, mesh=mesh_cfg, privacy=priv,
                       optimizer=OptimizerConfig(name="adamw"))
        state_sds = jax.eval_shape(
            lambda: steps_mod.init_train_state(model, rc, jax.random.PRNGKey(0)))
        batch_sds = specs_mod.batch_specs(cfg, shape)
        key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
        step = steps_mod.build_train_step(model, rc, abstract_mesh=mesh)
        with jax.set_mesh(mesh):
            st_specs = steps_mod.state_pspecs(state_sds)
            b_specs = steps_mod.batch_pspec(batch_sds, mesh_cfg.silo_axes)
        in_shardings = (st_specs, b_specs, P())
        out_shardings = (st_specs, jax.tree.map(lambda _: P(), {
            "loss": 0, "grad_norm_mean": 0, "clip_bound": 0, "lr": 0}))
        return (step, (state_sds, batch_sds, key_sds), in_shardings,
                out_shardings, (0,), mesh, model)

    # serving shapes
    params_sds = specs_mod.params_specs(model)
    cache_sds = specs_mod.cache_specs(cfg, shape, model)
    batch_sds = specs_mod.batch_specs(cfg, shape)
    with jax.set_mesh(mesh):
        p_specs = params_pspecs(params_sds)
        c_specs = cache_pspecs(cache_sds, mesh_cfg)
        b_specs = steps_mod.batch_pspec(batch_sds, mesh_cfg.silo_axes)
    logits_spec = spec_for(("batch", "vocab"),
                           (shape.global_batch, cfg.vocab_size))

    if shape.kind == "prefill":
        def step(params, batch, cache):
            return model.prefill(params, batch, cache)
    else:
        def step(params, batch, cache):
            return model.decode_step(params, batch, cache)

    in_shardings = (p_specs, b_specs, c_specs)
    out_shardings = (logits_spec, c_specs)
    return (step, (params_sds, batch_sds, cache_sds), in_shardings,
            out_shardings, (2,), mesh, model)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             sync_path: str = "fused", verbose: bool = True) -> dict:
    mesh_cfg = mesh_config(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicability(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_cfg.shape,
                "status": "skipped", "reason": reason}

    t0 = time.time()
    step, args, in_sh, out_sh, donate, mesh, model = build_cell(
        arch, shape_name, mesh_cfg, sync_path)
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        if verbose:
            print(mem)  # proves it fits
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        if verbose:
            print({k: ca.get(k) for k in ("flops", "bytes accessed")})
        hlo = compiled.as_text()
    devices_per_pod = 256
    summary = hlo_cost.analyze(hlo, devices_per_pod=devices_per_pod)

    n_dev = mesh_cfg.n_devices
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_params = param_count(cfg)
    n_active = active_param_count(cfg)
    if shape.kind == "train":
        model_flops = 6.0 * n_active * tokens
    else:
        model_flops = 2.0 * n_active * tokens
    hlo_flops_chip = summary.flops
    t_compute = hlo_flops_chip / PEAK_FLOPS_BF16
    t_memory = summary.hbm_bytes / HBM_BW
    t_coll = summary.total_collective / ICI_BW
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": list(mesh_cfg.shape), "axes": list(mesh_cfg.axes),
        "status": "ok", "sync_path": sync_path,
        "params_B": n_params / 1e9, "active_params_B": n_active / 1e9,
        "tokens": tokens,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes_per_dev": mem.argument_size_in_bytes,
            "output_bytes_per_dev": mem.output_size_in_bytes,
            "temp_bytes_per_dev": mem.temp_size_in_bytes,
            "alias_bytes_per_dev": mem.alias_size_in_bytes,
        },
        "xla_cost_analysis": {"flops": ca.get("flops"),
                              "bytes_accessed": ca.get("bytes accessed")},
        "hlo_cost": {
            "flops_per_chip": hlo_flops_chip,
            "hbm_bytes_per_chip": summary.hbm_bytes,
            "collective_bytes_weighted": summary.collective_bytes,
            "collective_bytes_raw": summary.collective_raw,
            "cross_pod_bytes": summary.cross_pod_bytes,
            "while_trip_counts": summary.trip_counts,
        },
        "roofline": {
            "t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_coll, "dominant": dominant,
            "model_flops_global": model_flops,
            "hlo_flops_global": hlo_flops_chip * n_dev,
            "useful_flops_ratio": model_flops / max(hlo_flops_chip * n_dev, 1.0),
            "roofline_fraction": (model_flops / n_dev / PEAK_FLOPS_BF16)
            / max(t_compute, t_memory, t_coll, 1e-30),
        },
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--sync-path", default="fused", choices=("fused", "barrier"))
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = (runnable_cells() if args.all
             else [(args.arch, args.shape)])

    n_ok = n_fail = n_skip = 0
    for arch, shape_name in cells:
        for multi in meshes:
            tag = "multi" if multi else "single"
            dest = out_dir / tag / f"{arch}__{shape_name}.json"
            dest.parent.mkdir(parents=True, exist_ok=True)
            print(f"=== {arch} x {shape_name} x {tag} ===", flush=True)
            try:
                rec = run_cell(arch, shape_name, multi, args.sync_path)
                if rec["status"] == "skipped":
                    n_skip += 1
                else:
                    n_ok += 1
                    r = rec["roofline"]
                    print(f"  dominant={r['dominant']} "
                          f"t=({r['t_compute_s']:.3e},{r['t_memory_s']:.3e},"
                          f"{r['t_collective_s']:.3e})s "
                          f"roofline_frac={r['roofline_fraction']:.3f}", flush=True)
            except Exception as e:
                n_fail += 1
                rec = {"arch": arch, "shape": shape_name, "status": "failed",
                       "mesh": "multi" if multi else "single",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                print(f"  FAILED: {type(e).__name__}: {str(e)[:200]}", flush=True)
            dest.write_text(json.dumps(rec, indent=2, default=float))
    print(f"done: ok={n_ok} skipped={n_skip} failed={n_fail}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
