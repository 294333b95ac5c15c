"""Device time of the model's forward and backward pass per training step
(ms): self time of the ops whose innermost program scope is
``train.fwd_bwd`` (``distributed/steps.py``), averaged over the chips."""
from bench.lib.program_trace import scope_ms_per_step

LAYER = "train step"
MOVES = "train_tokens_per_s"


def read(run):
    return scope_ms_per_step(run, lambda s: s == "train.fwd_bwd")
