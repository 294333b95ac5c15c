"""Share of the serving ticks' wall time that the chip's peaks would need
(%): per tick, the larger of the model FLOPs of its valid rows / peak FLOP/s
and the bytes it must read (the weights once per program call plus the live
KV pages) / peak HBM bandwidth, summed over ticks, over the ticks' summed
wall time (the benchmark's span around ``ContinuousServer.step``)."""
from bench.lib.readers import tick_share

LAYER = "serving tick"
MOVES = "serve_itl_p95_ms"


def read(run):
    return tick_share(run)
