"""Share of the table pages the paged kernel walks that hold live tokens
(%): the server's ``pages_live`` over ``pages_walked`` counted over the
window, from the increments each ``serve.dispatch`` span carries."""
from bench.lib.program_trace import arg_share

LAYER = "paged kernels"
MOVES = "serve_itl_p95_ms"


def read(run):
    return arg_share(run, "serve.dispatch", "pages_live", "pages_walked")
