"""Records ``data/cpu_trace.xplane.pb``, the small CPU trace the reduction
tests read: a ``bench.window`` span around three ``bench.tick`` spans, each
running one jitted matmul, with a sleep between ticks (idle gaps).

    JAX_PLATFORMS=cpu python3 bench/tests/make_cpu_trace.py
"""
import glob
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

OUT = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d, profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(3):
                    with jax.profiler.TraceAnnotation("bench.tick"):
                        f(x).block_until_ready()
                    time.sleep(0.002)
        shutil.copy(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0], OUT)


if __name__ == "__main__":
    main()
