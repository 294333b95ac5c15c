"""Training launcher: ``python -m repro.launch.train --arch <id> [options]``.

Thin CLI over :class:`repro.api.Session` — runs real steps on the available
devices (reduced smoke config by default; the full configs are dry-run-only
on CPU). On a TPU deployment the same entrypoint runs the full config; the
mesh comes from the runtime device set.
"""
from __future__ import annotations

import argparse
import json

from repro.api import Session
from repro.configs.base import OptimizerConfig, PrivacyConfig
from repro.launch.compile_cache import enable_compile_cache


def run_wire(args):
    """Wire-tier demo: N fault-tolerant component-protocol rounds on the
    MNIST-MLP3 model (the examples/collaborative_mnist.py setup), with
    optional deadline/quorum closure, seeded chaos and a crash-consistent
    journal (docs/failure_model.md)."""
    import os

    import jax
    import jax.numpy as jnp

    from repro.api import CollaborativeSession
    from repro.configs.paper_models import MNIST_MLP3
    from repro.core.tee.faults import FaultInjector, FaultPlan, RoundJournal
    from repro.data.synthetic import synthetic_mnist
    from repro.models.small import build_small_model

    n = args.silos
    rounds = args.wire_rounds
    sm = build_small_model(MNIST_MLP3)
    params = sm.init(jax.random.PRNGKey(1))
    train, _ = synthetic_mnist(n_train=1024, n_test=256)
    silo_data = [{"x": jnp.asarray(s.x), "y": jnp.asarray(s.y)}
                 for s in train.split(n)]
    priv = PrivacyConfig(enabled=not args.no_privacy, sigma=args.sigma,
                         clip_bound=1.0)
    sess = CollaborativeSession.from_silos(silo_data, priv,
                                           params_template=params)

    def grad_fn(p, data):
        return jax.value_and_grad(sm.loss)(p, data)

    def update_fn(p, update, lr):
        return jax.tree.map(lambda a, u: a - lr * u.astype(a.dtype),
                            p, update)

    chaos = None
    if args.chaos_seed is not None:
        quorum = args.quorum or max(2, (2 * n) // 3)
        plan = FaultPlan.from_seed(args.chaos_seed, n, rounds, quorum=quorum)
        print(f"chaos plan seed={plan.seed}: {plan.counts()}")
        chaos = FaultInjector(plan)

    journal = None
    if args.journal:
        if os.path.exists(args.journal):
            journal = RoundJournal.load(args.journal)
            params = sess.resume(journal)
            print(f"resumed from {args.journal}: "
                  f"{journal.rounds_done} rounds already committed")
        else:
            journal = RoundJournal(path=args.journal)

    params, losses = sess.run(params, grad_fn, update_fn, args.lr, rounds,
                              round_timeout_s=args.round_timeout,
                              quorum=args.quorum, chaos=chaos,
                              journal=journal)
    print(f"wire tier: {len(losses)} rounds closed, "
          f"final loss={losses[-1]:.4f}"
          + (f" eps={sess.epsilon():.3f}" if priv.enabled else ""))
    st = sess.fault_stats
    print("fault stats: " + ", ".join(
        f"{k}={len(v) if isinstance(v, list) else v}"
        for k, v in sorted(st.items())))
    if args.spend_report:
        report = sess.privacy_report()
        if report is not None:
            with open(args.spend_report, "w") as f:
                json.dump(report, f, indent=1)
            print(f"spend report written to {args.spend_report}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true",
                    help="full config (TPU deployment); default: smoke config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--sigma", type=float, default=0.5)
    ap.add_argument("--lam", type=float, default=0.0)
    ap.add_argument("--dynamic-clip", action="store_true")
    ap.add_argument("--no-privacy", action="store_true")
    ap.add_argument("--silos", type=int, default=4)
    ap.add_argument("--sync-path", default="fused", choices=("fused", "barrier"))
    ap.add_argument("--mask-mode", default="pairwise",
                    choices=("pairwise", "admin", "none"),
                    help="zero-sum mask construction: key-derived pairwise "
                         "(default), the paper-faithful O(n*P) admin masks, "
                         "or none (confidentiality-only)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--epsilon-budget", type=float, default=None,
                    help="global budget: stop once the session epsilon "
                         "reaches this")
    ap.add_argument("--silo-epsilon-budget", type=float, default=None,
                    help="per-silo budget: a silo whose own epsilon (over "
                         "the steps it contributed to) reaches this is "
                         "excluded from the participation set, no rejoin "
                         "without operator override; training stops once no "
                         "silo may contribute")
    ap.add_argument("--spend-report", default=None, metavar="PATH",
                    help="write the ledger's per-silo spend report JSON here "
                         "at exit")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--elastic", action="store_true",
                    help="thread a per-step silo participation set through "
                         "the step; straggler escalations drop a silo for a "
                         "cooldown window (DP invariants preserved)")
    ap.add_argument("--drop-silos", default=None,
                    help="deterministic dropout demo: comma-separated "
                         "step:silo[:cooldown] triples, e.g. '10:3:5,20:2' "
                         "(silo 3 out for steps 10-14, silo 2 out from 20 on)")
    ap.add_argument("--wire-rounds", type=int, default=None, metavar="N",
                    help="run N rounds of the wire-tier component protocol "
                         "(CollaborativeSession on the MNIST-MLP3 demo "
                         "model) instead of the fused trainer; combine with "
                         "--round-timeout/--quorum/--chaos-seed for "
                         "fault-tolerant rounds (docs/failure_model.md)")
    ap.add_argument("--round-timeout", type=float, default=None, metavar="S",
                    help="wire tier: per-round deadline in seconds; the "
                         "round closes at the deadline once a quorum of "
                         "updates has landed, non-responders are dropped "
                         "and the round replays over the realized set")
    ap.add_argument("--quorum", type=int, default=None,
                    help="wire tier: minimum responders to close a round "
                         "(also the membership drop floor)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="wire tier: inject a seeded FaultPlan (crashes, "
                         "hangs, drops, corruption, KDS denials, updater "
                         "crashes) — replayable chaos for the tolerant path")
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="wire tier: crash-consistent round journal; if the "
                         "file exists the run RESUMES from it")
    args = ap.parse_args()
    enable_compile_cache()

    if args.wire_rounds is not None:
        return run_wire(args)
    if args.round_timeout is not None or args.quorum is not None \
            or args.chaos_seed is not None or args.journal is not None:
        raise SystemExit("--round-timeout/--quorum/--chaos-seed/--journal "
                         "are wire-tier options: add --wire-rounds N")

    sess = Session.from_config(
        args.arch, full=args.full,
        privacy=PrivacyConfig(enabled=not args.no_privacy, sigma=args.sigma,
                              clip_bound=1.0, dynamic_clip=args.dynamic_clip,
                              noise_lambda=args.lam, n_silos=args.silos,
                              sync_path=args.sync_path,
                              mask_mode=args.mask_mode),
        optimizer=OptimizerConfig(name="adamw", lr=args.lr))

    silo_schedule = None
    if args.drop_silos:
        # size the schedule by the count the step actually aggregates over
        # (the barrier tier pins it to the mesh's silo extent, not --silos)
        from repro.distributed.steps import effective_n_silos
        n_silos = effective_n_silos(sess.run_cfg)
        drops = []
        for spec in args.drop_silos.split(","):
            parts = [int(x) for x in spec.split(":")]
            step0, silo = parts[0], parts[1]
            cooldown = parts[2] if len(parts) > 2 else 0
            if silo >= n_silos:
                print(f"warning: --drop-silos silo {silo} ignored "
                      f"(step aggregates over {n_silos} silos)")
                continue
            drops.append((step0, silo, cooldown))

        # stateless step -> mask, so the schedule holds across checkpoint
        # resume (a run restored past step0 still sees the drop in effect)
        def silo_schedule(step, _d=drops, _n=n_silos):
            import numpy as np
            active = np.ones(_n, bool)
            for step0, silo, cooldown in _d:
                if step >= step0 and (cooldown == 0 or step < step0 + cooldown):
                    active[silo] = False
            return active

    result = sess.train(steps=args.steps, batch_size=args.batch,
                        seq_len=args.seq, checkpoint_dir=args.checkpoint_dir,
                        checkpoint_every=25, log_every=10,
                        epsilon_budget=args.epsilon_budget,
                        silo_epsilon_budget=args.silo_epsilon_budget,
                        elastic=args.elastic, silo_schedule=silo_schedule)
    final = result.final
    print(f"done at step {result.step}: loss={final.get('loss', float('nan')):.4f}"
          + (f" eps={final.get('epsilon'):.3f}" if "epsilon" in final else "")
          + (f" contributions={final.get('n_contributions'):.0f}"
             if "n_contributions" in final else ""))

    report = sess.privacy_report()
    if report is not None:
        from repro.analysis.report import privacy_spend_table
        print("\nprivacy spend report (per-silo ledger):")
        print(privacy_spend_table(report))
        if args.spend_report:
            with open(args.spend_report, "w") as f:
                json.dump(report, f, indent=1)
            print(f"spend report written to {args.spend_report}")


if __name__ == "__main__":
    main()
