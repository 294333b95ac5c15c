"""As ``mfu.chat``, over the ticks that ran a prefill chunk, counting only
the prefill call's valid prompt rows (%)."""
from bench.lib.readers import tick_share

LAYER = "serving tick"
MOVES = "serve_ttft_p95_ms"


def read(run):
    return tick_share(run, prefill_only=True)
