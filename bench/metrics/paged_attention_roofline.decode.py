"""Byte-bound roofline share of the Pallas paged-attention kernel (%): the
bytes of the live tokens' K/V pages plus the q rows read and out rows
written, every layer of every call in the window, / the chip's HBM bandwidth
/ the kernel's device time (``KERNEL``, its name in a TPU trace)."""
from bench.lib.readers import kernel_ns, paged_kernel_bytes, roofline

LAYER = "paged kernels"
MOVES = "serve_tokens_per_s"
KERNEL = r"^%paged_attention_pallas(\.\d+)?$"


def read(run):
    return roofline(paged_kernel_bytes(run), kernel_ns(run, KERNEL),
                    run.peaks["hbm_bytes_per_s"])
