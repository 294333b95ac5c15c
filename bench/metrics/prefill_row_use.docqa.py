"""Share of the chunk program's rows that hold prompt tokens (%): the
server's ``prefill_rows_valid`` over ``prefill_rows_computed`` (slots x
chunk per call) counted over the window, from the increments each
``serve.dispatch`` span carries."""
from bench.lib.program_trace import arg_share

LAYER = "serving tick"
MOVES = "serve_itl_p95_ms"


def read(run):
    return arg_share(run, "serve.dispatch", "rows_valid", "rows_computed")
