"""Roofline share of the SSD Pallas kernel (%): the least time its work
needs at the chip's peaks, the larger of its FLOPs / peak FLOP/s and its
bytes / HBM bandwidth, both from the kernel's shapes in every chunk call of
the window (``zamba2.ssd_kernel_counts``; decode takes the one-step update
and runs no kernel), over the kernel's device time (``KERNEL``, its name in
a TPU trace)."""
from bench.lib.readers import kernel_ns
from bench.lib.zamba2 import for_run, ssd_kernel_counts, window_calls

LAYER = "SSD kernel"
MOVES = "serve_itl_p95_ms"
KERNEL = r"^%ssd_pallas(\.\d+)?$"


def read(run):
    s, ns = for_run(run), kernel_ns(run, KERNEL)
    if s is None or not ns:
        return None
    slots = run.mix["server"]["max_batch"]
    flops = byts = 0.0
    for C, _, _ in window_calls(run):
        if C > 1:
            f, b = ssd_kernel_counts(s, slots, C)
            flops, byts = flops + f, byts + b
    least = max(flops / run.peaks["flops_per_s"],
                byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9) if least > 0 else None
