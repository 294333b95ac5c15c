"""Host time of the logits read-out per serving tick (ms): the time inside
the program's ``serve.fetch`` (logits to the host) and ``serve.sample``
(argmax, append, finish) spans during which the device is idle, over the
window's ``serve.tick`` spans."""
from bench.lib.program_trace import idle_in_spans_per_tick

LAYER = "scheduler"
MOVES = "serve_itl_p95_ms"


def read(run):
    return idle_in_spans_per_tick(run, ("serve.fetch", "serve.sample"))
