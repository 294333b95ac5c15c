"""Training driver: ``Trainer.fit`` of a ``Session`` on the configured DP
tier, then the comparison with the plain reference.

Set-up builds one session and trainer, feeds it the bench's weights and
token rows, and drives its first ``checked_steps`` steps through the same
``fit`` call and feed the window uses. The window then runs whole chunks of
steps through ``fit`` until ``--seconds`` have passed, and ends when the
last state is ready. The comparison reads, against the reference's steps
from the same weights, rows and protocol keys:

* ``loss``: the widest relative gap of the checked steps' losses;
* ``grad``: per leaf, the norm of the first gradient the optimizer got, with
  the reference's first-step noise taken out, against the reference's
  clipped mean; the worst leaf's gap over max(its norm, the median leaf's);
* ``change``: per leaf, ||p_T - p_0|| after the checked steps against the
  reference's, the worst leaf's gap over max(its norm, the median leaf's);
  leaves whose reference gradient is under 1e-3 of the median leaf's (a
  key bias under softmax) are left out.

With ``ctx.control`` the reference one precision lower, and with
``ctx.fault == "half_batch"`` the reference on half of each silo's rows,
stand in the program's place and give the same three numbers
(``out["control"]``, ``out["fault"]``), which the harness then judges.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.lib import reference, weights
from bench.lib.model import program_config

N_BATCHES = 1024  # distinct token batches drawn per run; the window cycles


def _worst_gap(prog: dict, ref: dict, keep=None) -> float:
    med = float(np.median(list(ref.values())))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in ref if keep is None or k in keep]
    return max(gaps) if gaps else float("nan")


def readings(prog: dict, ref: dict) -> dict:
    """The three compared numbers from the program's (or a stand-in's)
    readings ``prog`` and the reference's ``ref``."""
    lg = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                  ref["losses"]))
    g = ref["clip_mean_norms"]
    med = float(np.median(list(g.values())))
    moving = {k for k, v in g.items() if v >= 1e-3 * med}
    return {"loss": lg,
            "grad": _worst_gap(prog["clip_mean_norms"], g),
            "change": _worst_gap(prog["change_norms"], ref["change_norms"],
                                 moving)}


def run(ctx):
    import jax

    from repro.api import Session
    from repro.configs.base import MeshConfig, OptimizerConfig, PrivacyConfig

    mix, s = ctx.mix, ctx.sizes
    n_silos = int(mix["n_silos"])
    rows = n_silos * int(mix["rows_per_silo"])
    seq = int(mix["seq_len"])
    checked = int(mix["checked_steps"])
    o = mix["optimizer"]
    priv = PrivacyConfig(enabled=True, sigma=mix["sigma"],
                         clip_bound=mix["clip_bound"],
                         noise_lambda=mix["noise_lambda"],
                         mask_mode=mix["mask_mode"], sync_path=mix["sync_path"],
                         n_silos=n_silos)
    opt = OptimizerConfig(name=o["name"], lr=o["lr"], beta1=o["beta1"],
                          beta2=o["beta2"], eps=o["eps"],
                          weight_decay=o["weight_decay"])
    seed31 = weights.seed31(ctx.seed)
    sess = Session.from_config(program_config(s), privacy=priv, optimizer=opt,
                               mesh=MeshConfig((ctx.chips,), ("data",)),
                               seed=seed31)
    params = weights.make(s, ctx.seed)
    sess.model = dataclasses.replace(sess.model, init=lambda key: params)
    toks = weights.token_rows(ctx.seed, N_BATCHES, rows, seq + 1, s.vocab)
    take = jax.jit(lambda t, i: {"tokens": t[i, :, :-1], "labels": t[i, :, 1:]})
    fed = [0]

    def next_batch():
        i = fed[0] % N_BATCHES
        fed[0] += 1
        return take(toks, i)

    ctx.hook("session", sess)
    trainer = sess.trainer(total_steps=1, checkpoint_every=10**9, log_every=0,
                           next_batch=next_batch)
    ctx.hook("trainer", trainer)
    state = sess.init_state()
    root = jax.random.PRNGKey(seed31 + 1)

    def fit(n, state):
        trainer.tcfg.total_steps = n
        state, _ = trainer.fit(state, root)
        return state

    # the checked steps, through the window's own call and feed
    state = fit(1, state)
    first_grad = jax.device_get(jax.tree.map(
        lambda m: m / np.float32(1 - o["beta1"]), state.opt_state["m"]))
    state = fit(checked - 1, state)
    p0 = weights.make(s, ctx.seed)
    change = {k: float(v) for k, v in reference.leaf_norms(
        jax.tree.map(lambda a, b: a - b, state.params, p0)).items()}
    del p0
    losses = [float(e["loss"]) for e in trainer.metrics_log[:checked]]
    ctx.setup_done()

    steps, per_step = 0, None
    with ctx.window() as win:
        while True:
            left = ctx.seconds - (time.perf_counter() - win.t0)
            if left <= 0:
                break
            k = 4 if per_step is None else min(16, int(left / per_step + 0.5))
            if k < 1:
                break
            with ctx.span("bench.fit"):
                state = fit(k, state)
            steps += k
            per_step = (time.perf_counter() - win.t0) / steps
        jax.block_until_ready(state)
    window_s = win.seconds
    ctx.after_window()
    window_losses = [float(e["loss"]) for e in trainer.metrics_log[checked:]]
    tokens = steps * rows * seq
    del state, trainer, sess, toks, params
    ctx.free_device()

    out = {"metrics": {"train_tokens_per_s": tokens / window_s},
           "attempted": steps,
           "failed": int(sum(not np.isfinite(x) for x in window_losses)),
           "records": {"steps": steps, "tokens": tokens, "seq_len": seq,
                       "window_s": window_s}}

    # the comparison, after the window and with the program's state freed
    root = jax.random.PRNGKey(seed31 + 1)
    batches = np.asarray(weights.token_rows(ctx.seed, N_BATCHES, rows,
                                            seq + 1, s.vocab)[:checked])
    p0 = weights.make(s, ctx.seed)
    kw = dict(n_silos=n_silos, clip=mix["clip_bound"], sigma=mix["sigma"],
              lam=mix["noise_lambda"], opt=o)
    ref = reference.dp_steps(s, p0, batches, root, program_first_grad=first_grad,
                             **kw)
    prog = {"losses": losses, "clip_mean_norms": ref.pop(
        "program_clip_mean_norms"), "change_norms": change}
    out["readings"] = readings(prog, ref)
    if ctx.control:
        ctl = reference.dp_steps(s, p0, batches, root,
                                 precision=reference.CONTROL, **kw)
        out["control"] = readings(ctl, ref)
    if ctx.fault == "half_batch":
        half = reference.dp_steps(s, p0, batches, root, rows_used=0.5, **kw)
        out["fault"] = readings(half, ref)
    return out
