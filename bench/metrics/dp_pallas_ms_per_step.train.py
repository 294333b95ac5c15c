"""Device time of the DP engine's Pallas kernels per training step (ms),
averaged over the chips (``readers.DP_KERNELS``: the per-silo clipped sum,
the fused clip + mask + noise kernel and the batched corrected-noise
kernel)."""
from bench.lib.readers import DP_KERNELS, kernel_ns

LAYER = "DP kernels"
MOVES = "train_tokens_per_s"


def read(run):
    ns = kernel_ns(run, DP_KERNELS)
    steps = run.records.get("steps")
    return ns / steps * 1e-6 if ns and steps else None
