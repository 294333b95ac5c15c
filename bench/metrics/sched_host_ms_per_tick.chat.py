"""Host time per serving tick (ms): inside the benchmark's span around each
``ContinuousServer.step``, the time during which the device is idle,
averaged over ticks."""
from bench.lib.readers import host_idle_in_spans

LAYER = "scheduler"
MOVES = "serve_itl_p95_ms"


def read(run):
    return host_idle_in_spans(run, "bench.tick")
