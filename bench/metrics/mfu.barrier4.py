"""Model FLOP/s utilisation of the whole training step over the cell's
chips (%): model FLOPs per token (6 x the matmul parameters, the tied head
counted, the embedding gather not, plus 3 x the forward score and value
matmuls with causal attention counted half) x tokens per second of the
traced window, every silo's tokens, over chips x the chip's peak FLOP/s.
Recomputed work is not counted."""
LAYER = "train step"
MOVES = "train_tokens_per_s"


def read(run):
    rec = run.records
    if not rec.get("steps") or run.trace is None:
        return None
    rate = rec["tokens"] / (run.trace.window_ns * 1e-9)
    flops = run.sizes.train_flops_per_token(rec["seq_len"])
    return 100.0 * rate * flops / (run.chips * run.peaks["flops_per_s"])
