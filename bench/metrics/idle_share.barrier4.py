"""Share of the traced window in which no operation ran on the device (%):
1 - union of the device op intervals / window, the mean over the cell's
chips."""
from bench.lib.readers import idle_share

LAYER = "device"
MOVES = "train_tokens_per_s"


def read(run):
    return idle_share(run)
