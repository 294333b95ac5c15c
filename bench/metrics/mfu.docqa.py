"""Share of the serving ticks' wall time that the chip's peaks would need
(%), from Zamba2's model counts (``bench/lib/zamba2.py``): per tick, the
larger of the FLOPs of its calls' valid rows (every matrix, the state
recurrence, each invocation's attention over the cache, the head) / peak
FLOP/s and the bytes they must read (the weights once per call, the live KV
pages, each valid slot's recurrent state read and written) / peak HBM
bandwidth, summed over ticks, over the ticks' summed wall time."""
from bench.lib.zamba2 import tick_share

LAYER = "serving tick"
MOVES = "serve_itl_p95_ms"


def read(run):
    return tick_share(run)
