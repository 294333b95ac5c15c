"""Serving driver for Zamba2 configurations: the loop and the comparison of
``bench/lib/serve.py``, run with the hybrid family's program configuration,
weights and plain reference (``bench/lib/zamba2.py``,
``bench/lib/reference_zamba2.py``) bound in place of the dense ones for the
length of the run.

The harness hands every driver the dense ``Sizes`` it reads from the cell's
configuration file, named after that configuration; this driver reads the
same file into Zamba2's sizes, and names it in its records
(``zamba2_config``) for the Zamba2 readers in ``bench/metrics``.

Beside ``serve.py``'s ``token_gap`` (the widest gap), each of the program,
the control and the fault also reads ``mean_gap``: the mean over every
compared token of the gap by which its logit lies below the reference's
best. A bfloat16 program puts another token first at many positions, each
by a small margin, which the mean counts and the widest gap does not."""
from __future__ import annotations

import contextlib

from bench.lib import reference_zamba2, serve, zamba2


def sizes(ctx) -> zamba2.Sizes:
    """Zamba2's sizes of the cell's configuration."""
    return zamba2.load(ctx.sizes.name)


@contextlib.contextmanager
def _bound(module, **names):
    saved = {k: getattr(module, k) for k in names}
    for k, v in names.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in saved.items():
            setattr(module, k, v)


def run(ctx):
    s = sizes(ctx)
    ctx.sizes = s
    gaps = []  # serve.token_gaps' results in call order
    token_gaps = serve.token_gaps

    def recorded(*a, **kw):
        gaps.append(token_gaps(*a, **kw))
        return gaps[-1]

    with _bound(serve, program_config=zamba2.program_config, weights=zamba2,
                reference=reference_zamba2, token_gaps=recorded):
        out = serve.run(ctx)
    out["records"]["zamba2_config"] = s.name
    mean = [float(g.mean()) if g.size else float("inf") for g in gaps]
    out["readings"]["mean_gap"] = mean[0]
    for name in ("control", "fault"):  # the program's comparison comes first
        if name in out:
            out[name]["mean_gap"] = mean[1 if name == "control" else -1]
    return out
