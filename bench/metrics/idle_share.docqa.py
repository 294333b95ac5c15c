"""Share of the traced window in which no operation ran on the device (%):
1 - union of the device op intervals / window, averaged over the chips."""
from bench.lib.readers import idle_share

LAYER = "device"
MOVES = "serve_itl_p95_ms"


def read(run):
    return idle_share(run)
