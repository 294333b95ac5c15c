"""Device time of the barrier tier's aggregation per training step (ms):
self time of the ops whose innermost program scope is ``dp.psum`` (the
packed psum over the silos in ``distributed/steps.py``), the mean over the
cell's chips."""
from bench.lib.program_trace import scope_ms_per_step

LAYER = "aggregation"
MOVES = "train_tokens_per_s"


def read(run):
    return scope_ms_per_step(run, lambda s: s == "dp.psum")
