"""Whole runs of the harness on the CPU at a tiny size, past its look for a
chip: a sound run is correct; with the timed path broken underneath
(state returned unchanged, half of each silo's batch left out, a served
token altered where it is produced) ``correct`` comes out false. And a cell
whose mix and per-layer metric exist only in this test runs without any
edit to the harness."""
import dataclasses
import json
import shutil
from pathlib import Path


from bench.lib import harness

BENCH = Path(__file__).resolve().parents[1]
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "vocab_size": 512, "num_hidden_layers": 2,
        "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "tie_word_embeddings": True, "model_type": "qwen2"}
TRAIN, CHAT = "train.qwen2.5-3b", "serve-chat.qwen2.5-3b"


def small_train_mix():
    mix = json.loads((BENCH / "traffic" / "train.json").read_text())
    mix.update(rows_per_silo=2, seq_len=32)
    return mix


def small_serve_mix(name="chat"):
    mix = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    mix["server"].update(max_batch=4, max_len=128, prefill_chunk=16)
    for k in ("prompt_tokens", "output_tokens"):
        mix[k].update(min=4, max=48)
        if "median" in mix[k]:
            mix[k].update(median=16, p95=40)
    mix["rate_per_s"] = 4.0
    return mix


def run(cell, mix, hooks=None, **kw):
    return harness.run_cell(cell, 2**31 + 99, 1.5, False, require_chip=False,
                            config_override=TINY, mix_override=mix,
                            hooks=hooks, **kw)


def test_sound_train_run_is_correct():
    r = run(TRAIN, small_train_mix())
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_state_returned_unchanged_is_not_correct():
    def break_trainer(trainer):
        import jax
        import jax.numpy as jnp

        step = trainer._jit_step

        def unchanged(state, *args):
            _, metrics = step(jax.tree.map(jnp.copy, state), *args)
            return state, metrics
        trainer._jit_step = unchanged

    r = run(TRAIN, small_train_mix(), {"trainer": break_trainer})
    assert not r["correct"]
    assert r["checks"]["change"]["value"] > r["checks"]["change"]["limit"]


def test_half_the_batch_left_out_is_not_correct():
    def halve(sess):
        loss = sess.model.loss

        def half(params, batch):
            return loss(params, {k: v[:v.shape[0] // 2]
                                 for k, v in batch.items()})
        sess.model = dataclasses.replace(sess.model, loss=half)

    r = run(TRAIN, small_train_mix(), {"session": halve})
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_sound_serve_run_is_correct():
    r = run(CHAT, small_serve_mix())
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"serve_ttft_p95_ms", "serve_itl_p95_ms",
                                 "setup_s"}


def test_altered_served_token_is_not_correct():
    def alter(srv):
        append = srv._append

        def altered(i, tok):
            if len(srv.slots[i].req.generated) == 2:
                tok = (tok + 1) % srv.model.cfg.vocab_size
            return append(i, tok)
        srv._append = altered

    r = run(CHAT, small_serve_mix(), {"server": alter})
    assert not r["correct"]
    assert r["checks"]["token_gap"]["value"] > \
        r["checks"]["token_gap"]["limit"]


READER = '''
LAYER = "scheduler"
MOVES = "serve_itl_p95_ms"


def read(run):
    ticks = run.records.get("ticks", [])
    return len(ticks) / run.records["window_s"] if ticks else None
'''


def test_a_mix_and_a_metric_defined_only_here(tmp_path):
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = {"name": "serve-tiny.qwen2.5-3b", "config": "qwen2.5-3b.2l",
            "traffic": "tiny-burst", "chips": 1, "why": "test only"}
    spec["workloads"].append(cell)
    for m in spec["end_to_end"]:
        if m["name"] in ("serve_ttft_p95_ms", "serve_itl_p95_ms"):
            m["workloads"].append(cell["name"])
    spec["per_layer"].append({
        "name": "ticks_per_s.tiny", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "scheduler",
        "moves": "serve_itl_p95_ms", "workloads": [cell["name"]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for d in ("traffic", "metrics", "limits"):
        (tmp_path / "extra" / d).mkdir(parents=True)
    (tmp_path / "extra" / "traffic" / "tiny-burst.json").write_text(
        json.dumps(dict(small_serve_mix(), rate_per_s=6.0)))
    (tmp_path / "extra" / "metrics" / "ticks_per_s.tiny.py").write_text(READER)
    shutil.copy(BENCH / "limits" / f"{CHAT}.json",
                tmp_path / "extra" / "limits" / f"{cell['name']}.json")
    kw = dict(root=tmp_path, search=[tmp_path / "extra"],
              require_chip=False, config_override=TINY)
    r = harness.run_cell(cell["name"], 5, 1.5, False, **kw)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"serve_ttft_p95_ms", "serve_itl_p95_ms",
                                 "setup_s"}
    r = harness.run_cell(cell["name"], 6, 1.5, True, **kw)
    assert r["metrics"]["ticks_per_s.tiny"]["value"] > 0
    assert r["metrics"]["ticks_per_s.tiny"]["unit"] == "1/s"
    assert "window_s" in r["device"]


def test_no_chip_means_no_result(capsys):
    assert harness.main(["--workload", TRAIN, "--seed", "1", "--seconds",
                         "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""
