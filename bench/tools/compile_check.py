#!/usr/bin/env python3
"""Compile each cell's step programs for a described (not attached) TPU v5e
and print XLA's memory account of them. Nothing runs; this needs no chip.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_check.py [cell ...]

The program picks its Pallas kernels when ``jax.default_backend()`` is
``tpu``; this script makes the CPU process answer so, and must not share a
process with anything else.
"""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench.lib import harness, model as model_mod  # noqa: E402


def gib(n):
    return f"{n / 2**30:.2f} GiB"


def account(name, compiled):
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: arguments {gib(m.argument_size_in_bytes)} + temporaries "
          f"{gib(m.temp_size_in_bytes)} + outputs {gib(m.output_size_in_bytes)}"
          f" - aliased {gib(m.alias_size_in_bytes)} = {gib(peak)}; "
          f"{compiled.as_text().count('tpu_custom_call')} tpu_custom_call",
          flush=True)


def shaped(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                       sharding=sharding),
                        tree)


def train(cell, cfg, mix, dev):
    from repro.api import Session
    from repro.configs.base import MeshConfig, OptimizerConfig, PrivacyConfig

    s = model_mod.sizes(cfg, cell["config"])
    o = mix["optimizer"]
    sess = Session.from_config(
        model_mod.program_config(s),
        privacy=PrivacyConfig(enabled=True, sigma=mix["sigma"],
                              clip_bound=mix["clip_bound"],
                              noise_lambda=mix["noise_lambda"],
                              mask_mode=mix["mask_mode"],
                              sync_path=mix["sync_path"],
                              n_silos=mix["n_silos"]),
        optimizer=OptimizerConfig(name=o["name"], lr=o["lr"]),
        mesh=MeshConfig((1,), ("data",)))
    trainer = sess.trainer(total_steps=1, log_every=0)
    state = shaped(jax.eval_shape(sess.init_state), dev)
    rows = mix["n_silos"] * mix["rows_per_silo"]
    tok = jax.ShapeDtypeStruct((rows, mix["seq_len"]), jnp.int32,
                               sharding=dev)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dev)
    account(f"{cell['name']} train step", trainer._jit_step.lower(
        state, {"tokens": tok, "labels": tok}, key).compile())


def serve(cell, cfg, mix, dev):
    from repro.models.registry import build_model

    s = model_mod.sizes(cfg, cell["config"])
    sc = mix["server"]
    m = build_model(model_mod.program_config(s), compute_dtype=jnp.float32)
    params = shaped(jax.eval_shape(m.init, jax.random.PRNGKey(0)), dev)
    B, per = sc["max_batch"], -(-sc["max_len"] // sc["page_size"])
    pool = shaped(jax.eval_shape(lambda: m.init_paged_cache(
        B * per, sc["page_size"])), dev)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)
    fn = jax.jit(m.paged_step, donate_argnums=(2,))
    for C in (1, sc["prefill_chunk"]):
        account(f"{cell['name']} paged_step C={C}", fn.lower(
            params, i32(B, C), pool, i32(B, per), i32(B), i32(B)).compile())


def main():
    from jax.experimental import topologies

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    want = set(sys.argv[1:])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"  # the program's kernel dispatch asks
    dev = SingleDeviceSharding(topo.devices[0])
    for cell in spec["workloads"]:
        if want and cell["name"] not in want:
            continue
        cfg = harness.load_json(ROOT / "bench" / "configs"
                                / f"{cell['config']}.json")
        mix = harness.load_json(ROOT / "bench" / "traffic"
                                / f"{cell['traffic']}.json")
        {"train": train, "serve": serve}[mix["driver"]](cell, cfg, mix, dev)


if __name__ == "__main__":
    main()
