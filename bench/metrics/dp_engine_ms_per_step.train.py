"""Device time of the DP engine per training step (ms): self time of the
ops whose innermost program scope is a ``dp.*`` stage (``core/dp_pipeline.py``
and ``core/flatbuf.py``: packing, norms, clip, the clipped sum, noise,
unpacking), its Pallas kernels and the XLA glue around them alike, averaged
over the chips."""
from bench.lib.program_trace import scope_ms_per_step

LAYER = "DP engine"
MOVES = "train_tokens_per_s"


def read(run):
    return scope_ms_per_step(run, lambda s: s.startswith("dp."))
