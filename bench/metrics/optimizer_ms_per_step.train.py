"""Device time of the optimizer update per training step (ms): self time of
the ops whose innermost program scope is ``train.optimizer``
(``distributed/steps.py``: the mean over contributions and AdamW), averaged
over the chips."""
from bench.lib.program_trace import scope_ms_per_step

LAYER = "train step"
MOVES = "train_tokens_per_s"


def read(run):
    return scope_ms_per_step(run, lambda s: s == "train.optimizer")
