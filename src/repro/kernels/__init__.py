"""Kernel packages + the shared dispatch registry.

Importing this package registers every kernel variant (the seven ops modules)
with :data:`repro.kernels.dispatch.REGISTRY`, so introspection
(``available_impls``) sees the full table. Selection override: the
``force_impl`` context manager — see ``dispatch.py``.
"""
from repro.kernels.dispatch import (REGISTRY, available_impls, force_impl,
                                    kernel_variant, on_tpu)
from repro.kernels.dp_clip import ops as dp_clip_ops
from repro.kernels.dp_fused import ops as dp_fused_ops
from repro.kernels.flash_attention import ops as flash_attention_ops
from repro.kernels.mamba2 import ops as mamba2_ops
from repro.kernels.paged_attention import ops as paged_attention_ops
from repro.kernels.rwkv6 import ops as rwkv6_ops
from repro.kernels.zsmask import ops as zsmask_ops

__all__ = [
    "REGISTRY",
    "available_impls",
    "force_impl",
    "kernel_variant",
    "on_tpu",
    "dp_clip_ops",
    "dp_fused_ops",
    "flash_attention_ops",
    "mamba2_ops",
    "paged_attention_ops",
    "rwkv6_ops",
    "zsmask_ops",
]
