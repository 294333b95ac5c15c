"""Records ``data/scope_trace.xplane.pb``, the small CPU trace the scope
attribution tests read: inside a ``bench.window`` span, two calls of one
jitted function whose ops sit under two program scopes, ``train.fwd_bwd``
(a matmul, and a ``scan`` loop) and ``dp.noise`` (inside the loop's body,
so nested in the loop's op), beside ops under no program scope. CPU op
events carry only ``hlo_op`` and ``hlo_module``, so the scopes come from
the HLO modules stored in the trace.

    JAX_PLATFORMS=cpu python3 bench/tests/make_scope_trace.py
"""
import glob
import shutil
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).parent / "data" / "scope_trace.xplane.pb"


def step(x):
    with jax.named_scope("train.fwd_bwd"):
        y = jnp.tanh(x @ x)

        def body(h, _):
            with jax.named_scope("dp.noise"):
                h = jnp.sin(h @ x)
            return h, None

        y, _ = jax.lax.scan(body, y, None, length=4)
    return jnp.cos(y).sum()


def main():
    f = jax.jit(step)
    x = jnp.ones((256, 256)) / 256
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d, profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(2):
                    f(x).block_until_ready()
        shutil.copy(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0], OUT)


if __name__ == "__main__":
    main()
