"""p95 over requests of the time from admission to a slot to the first
token on the host (ms), stamped by the server on the host clock
(``Request.t_first_token - Request.t_admit``) and carried by the
``serve.first_token`` mark of each request whose first token lands in the
window."""
from bench.lib.program_trace import arg_p95

LAYER = "serving tick"
MOVES = "serve_ttft_p95_ms"


def read(run):
    return arg_p95(run, "serve.first_token", "prefill_ms")
