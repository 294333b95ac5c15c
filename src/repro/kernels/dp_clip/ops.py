"""Public API for fused per-example clipping, routed through the
kernel-dispatch registry.

Tensor-level kernels (``dp_clip_sumsq``, ``dp_clip_accumulate``) operate on
one (B, D) block. A whole pytree is clipped through one fused
``dp_fused_clip_sum`` dispatch on its packed buffer
(``kernels/dp_fused/ops.packed_clip_and_sum``)."""
from __future__ import annotations

from repro.kernels.dispatch import kernel_variant, on_tpu, REGISTRY
from repro.kernels.dp_clip import ref
from repro.kernels.dp_clip.dp_clip import clip_accumulate, per_example_sumsq

SUMSQ = "dp_clip_sumsq"
ACCUM = "dp_clip_accumulate"


def _blockable(ctx) -> bool:
    B, D = ctx["B"], ctx["D"]
    return B % min(8, B) == 0 and D % min(512, D) == 0


@kernel_variant(SUMSQ, "pallas", priority=100, predicate=_blockable,
                auto_predicate=lambda ctx: ctx["on_tpu"],
                doc="fused Pallas per-example sum-of-squares")
def _sumsq_pallas(g):
    return per_example_sumsq(g, interpret=not on_tpu())


@kernel_variant(SUMSQ, "jnp", priority=10, doc="jnp reference")
def _sumsq_jnp(g):
    return ref.per_example_sumsq_ref(g)


@kernel_variant(ACCUM, "pallas", priority=100, predicate=_blockable,
                auto_predicate=lambda ctx: ctx["on_tpu"],
                doc="fused Pallas clip-and-accumulate")
def _accum_pallas(g, scale):
    return clip_accumulate(g, scale, interpret=not on_tpu())


@kernel_variant(ACCUM, "jnp", priority=10, doc="jnp reference")
def _accum_jnp(g, scale):
    return ref.clip_accumulate_ref(g, scale)


def sumsq(g, impl: str = "auto"):
    return REGISTRY.dispatch(SUMSQ, impl,
                             {"B": g.shape[0], "D": g.shape[1]}, g)


def clipped_sum(g, scale, impl: str = "auto"):
    """sum_b g[b] * scale[b] over a (B, D) block — also the packed silo
    accumulate in distributed/steps.py (B = n_silos, D = P_padded)."""
    return REGISTRY.dispatch(ACCUM, impl,
                             {"B": g.shape[0], "D": g.shape[1]}, g, scale)

