"""Per-layer readers on hand-made records and traces: the serving tick's
share of the chip's peaks, the paged kernel's byte roofline, host time in
tick spans and the idle share."""
import numpy as np

from bench.lib import readers
from bench.lib.harness import Run
from bench.lib.model import Sizes
from bench.lib.trace import Trace

S = Sizes(name="tiny", n_layers=2, d_model=8, n_heads=4, n_kv_heads=2,
          d_head=2, d_ff=16, vocab=32, qkv_bias=True, tied=True,
          rope_theta=1e4, norm_eps=1e-6)
PEAKS = {"flops_per_s": 1e6, "hbm_bytes_per_s": 1e6}
MIX = {"server": {"page_size": 4}}


def run_with(records=None, trace=None):
    return Run(trace=trace, records=records or {}, sizes=S, peaks=PEAKS,
               chips=1, mix=MIX)


def test_tick_share_is_least_time_over_wall_time():
    c1 = (1, np.array([1, 0]), np.array([7, 0]))   # a decode call
    c2 = (4, np.array([0, 3]), np.array([0, 0]))   # a prefill chunk
    rec = {"calls": [c1, c2], "ticks": [(0.0, 1.0, 0, 2)]}
    r = run_with(rec)
    want = readers.least_time(r, c1) + readers.least_time(r, c2)
    assert np.isclose(readers.tick_share(r), 100 * want / 1.0)
    assert np.isclose(readers.tick_share(r, prefill_only=True),
                      100 * readers.least_time(r, c2) / 1.0)
    assert readers.tick_share(run_with({"calls": [], "ticks": []})) is None


def test_paged_roofline_and_idle_from_a_hand_trace():
    ops = {0: [("%paged_attention_pallas.3", 0, 400),
               ("%fusion.1", 500, 700)]}
    tr = Trace(ops=ops, spans=[("bench.tick", 0, 1000)], lo=0, hi=1000)
    rec = {"calls": [(1, np.array([1]), np.array([3]))]}
    r = run_with(rec, tr)
    ns = readers.kernel_ns(r, r"^%paged_attention_pallas(\.\d+)?$")
    assert ns == 400
    kv = 4 * S.kv_bytes_per_token()               # one page of 4 tokens
    q_out = 2 * 4 * S.n_heads * S.d_head * S.n_layers
    assert readers.paged_kernel_bytes(r) == kv + q_out
    assert np.isclose(readers.roofline(kv + q_out, ns, 1e6),
                      100 * (kv + q_out) / 1e6 / 400e-9)
    assert np.isclose(readers.idle_share(r), 100 * (1 - 600 / 1000))
    assert np.isclose(readers.host_idle_in_spans(r, "bench.tick"),
                      400 * 1e-6)                   # 100 + 300 ns idle
    assert readers.kernel_ns(r, r"^%nothing$") is None
