"""Byte-bound roofline share of the DP engine's Pallas kernels (%): the HBM
bytes the kernels must move per step, from their shapes, / the chip's HBM
bandwidth / their device time per step.

Bytes per step on the fused tier: the clipped sum reads the (n, P) stack
and writes P; the corrected-noise kernel reads and writes P. P is the
packed parameter buffer (each leaf padded to 128, the total to 1024),
4-byte floats. The threefry streams the kernels regenerate in VMEM are VPU
work that no published peak bounds, so this share reads the byte bound
only."""
import math

from bench.lib.readers import DP_KERNELS, kernel_ns, roofline
from bench.lib.weights import shapes

LAYER = "DP kernels"
MOVES = "train_tokens_per_s"


def packed_size(s) -> int:
    def leaves(t):
        for v in t.values():
            yield from leaves(v) if isinstance(v, dict) else [math.prod(v)]
    n = sum(-(-x // 128) * 128 for x in leaves(shapes(s)))
    return -(-n // 1024) * 1024


def read(run):
    ns = kernel_ns(run, DP_KERNELS)
    steps = run.records.get("steps")
    if not ns or not steps:
        return None
    P = packed_size(run.sizes)
    per_step = (run.mix["n_silos"] + 3) * P * 4
    return roofline(per_step * steps, ns, run.peaks["hbm_bytes_per_s"])
