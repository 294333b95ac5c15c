"""Device time of the Mamba2 mixers per serving tick (ms): self time of the
ops whose innermost program scope is ``paged.ssm_in`` (norm, in_proj,
conv), ``paged.ssd`` (the scan or the one-step update) or ``paged.ssm_out``
(D skip, gated norm, out_proj), over the window's ticks."""
from bench.lib.program_trace import of

LAYER = "mamba mixer"
MOVES = "serve_itl_p95_ms"
SCOPES = ("paged.ssm_in", "paged.ssd", "paged.ssm_out")


def read(run):
    pt = of(run)
    devs = sorted(pt.ops)[:run.chips] if pt else []
    ticks = len(run.records.get("ticks", []))
    if not devs or not ticks:
        return None
    ns = sum(v for d in devs for k, v in pt.scope_ns(d).items()
             if k in SCOPES) / len(devs)
    return ns / ticks * 1e-6 if ns > 0 else None
