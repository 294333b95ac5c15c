"""The control, at a size a CPU test can hold: the plain reference one
precision lower (bfloat16 parameters and activations), put in the program's
place and judged by the harness against the cell's own limits, exactly as a
run judges the program. In training it comes out not correct, while the
program's readings of the same run stay inside the limits. A served token's logit gap
grows with the model's width, so the serving case keeps the configuration's
own widths and cuts the server and the requests instead. On the chip, at the
cells' own sizes, ``bench/tools/readings.py --control`` gives the readings
the limits were set from (PERF.md)."""
import json
from pathlib import Path

from bench.lib import harness

BENCH = Path(__file__).resolve().parents[1]
SMALL = {"hidden_size": 256, "num_attention_heads": 4,
         "num_key_value_heads": 1, "intermediate_size": 1024,
         "vocab_size": 4096, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
         "rope_theta": 1e6, "tie_word_embeddings": True, "model_type": "qwen2"}


def limits(cell):
    return json.loads((BENCH / "limits" / f"{cell}.json").read_text())[
        "limits"]


def test_train_control_is_not_correct():
    mix = json.loads((BENCH / "traffic" / "train.json").read_text())
    mix.update(rows_per_silo=2, seq_len=128)
    cell = "train.qwen2.5-3b"
    r = harness.run_cell(cell, 2**32 + 5, 1.0, False, require_chip=False,
                         config_override=SMALL, mix_override=mix,
                         control=True)
    assert not r["correct"], r["checks"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
    assert r["_diag"]["stand_in"] == "control"
    prog = r["_diag"]["program"]
    assert all(prog[k] <= v for k, v in limits(cell).items()), prog


def test_serve_control_is_judged_in_the_programs_place():
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix["server"].update(max_batch=2, max_len=256, prefill_chunk=64)
    mix["prompt_tokens"].update(min=32, max=128, median=64, p95=120)
    mix["output_tokens"].update(min=32, max=96, median=64, p95=90)
    mix.update(rate_per_s=2.0, check_requests=4)
    cell = "serve-chat.qwen2.5-3b"
    r = harness.run_cell(cell, 5, 3.0, False, require_chip=False,
                         mix_override=mix, control=True)
    assert r["_diag"]["stand_in"] == "control"
    ctl = r["checks"]["token_gap"]
    prog = r["_diag"]["program"]["token_gap"]
    assert prog <= ctl["limit"]
    assert ctl["value"] > 10 * max(prog, 1e-3)
    # the serving limits were set against a highest-precision reference and
    # do not yet separate the control (PERF.md, Open questions)
    assert r["correct"] == (ctl["value"] <= ctl["limit"])
