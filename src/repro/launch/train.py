"""End-to-end HFL training driver — the paper's full pipeline (Fig 1):

  1. draw the wireless scenario,
  2. plan:   TSIA user assignment + SROA resource allocation,
  3. train:  Algorithm 1 on the (synthetic) dataset with deadline-based
             straggler mitigation driven by the planned per-user delays,
  4. report: accuracy + the eq-15 objective + simulated wall-clock/energy,
  with atomic checkpointing and resume-after-crash.

Usage:
  PYTHONPATH=src python -m repro.launch.train --dataset fashionmnist \
      --iters 10 --users 20 --edges 4 [--resume] [--ckpt-dir out/ckpt]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.ckpt import CheckpointManager
from repro.core import sroa, tsia, wireless
from repro.core.system_model import evaluate
from repro.data import make_dataset, partition_to_users
from repro.data.synthetic import DATASET_SHAPES
from repro.fed import straggler
from repro.fed.hfl import HflConfig, run_hfl
from repro.models import cnn
from repro.runtime import compile_cache, fault


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="fashionmnist",
                    choices=list(cnn.PAPER_CNNS))
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--users", type=int, default=20)
    ap.add_argument("--edges", type=int, default=4)
    ap.add_argument("--L", type=int, default=2)
    ap.add_argument("--K", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.2)
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="out/ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--straggler-quantile", type=float, default=0.9)
    ap.add_argument("--noniid-alpha", type=float, default=None)
    args = ap.parse_args(argv)
    compile_cache.enable()

    # ---- 1. scenario -------------------------------------------------
    spec = dataclasses.replace(
        wireless.ScenarioSpec(), N=args.users, M=args.edges,
        D_range=(50, 90),
        s_bytes=float(cnn.param_bytes(cnn.PAPER_CNNS[args.dataset])))
    scn = wireless.draw_scenario(args.seed, spec)
    print(f"[scenario] N={scn.N} M={scn.M} "
          f"B_total={float(scn.B_total)/1e6:.2f} MHz "
          f"s={float(scn.s_bits)/8e3:.0f} KB")

    # ---- 2. plan ------------------------------------------------------
    t0 = time.time()
    plan = tsia.solve(scn, lam=args.lam)
    res = plan.sroa
    cb = evaluate(scn, plan.assign, res.b, res.f, res.p, args.lam)
    print(f"[plan] TSIA+SROA in {time.time()-t0:.1f}s: "
          f"R={plan.R:.1f} (E={float(cb.E_sum):.1f} J, "
          f"T={float(cb.T_sum):.1f} s), "
          f"assign_iters={plan.history.total_iters}")

    delays = straggler.per_user_delay(scn, plan.assign, res.b, res.f, res.p)
    deadline = straggler.over_provision_deadline(
        delays, args.straggler_quantile)
    participate = straggler.jittered_participation(delays, deadline,
                                                   seed=args.seed)
    print(f"[straggler] per-edge-iter deadline={deadline:.2f}s "
          f"(keeps ~{100*args.straggler_quantile:.0f}% of users)")

    # ---- 3. data ------------------------------------------------------
    cfg = cnn.PAPER_CNNS[args.dataset]
    ds = make_dataset(args.dataset, n_train=4000, n_test=800,
                      shape=DATASET_SHAPES[args.dataset], seed=args.seed)
    sizes = np.asarray(np.asarray(scn.D), int)
    x_u, y_u, mask, sizes = partition_to_users(
        ds.x_train, ds.y_train, sizes, alpha=args.noniid_alpha,
        seed=args.seed)

    # ---- 4. train (with resume) ----------------------------------------
    mgr = CheckpointManager(args.ckpt_dir, keep=3)
    w0 = cnn.init_params(cfg, jax.random.PRNGKey(args.seed))
    start = 0
    if args.resume:
        tree, step = fault.recover_from_checkpoint(mgr, w0)
        if tree is not None:
            w0, start = tree, int(step)
            print(f"[resume] from checkpoint step {start}")

    hcfg = HflConfig(L=args.L, K=args.K, I=args.iters, lr=args.lr,
                     seed=args.seed)
    t0 = time.time()
    w, hist = run_hfl(cfg, w0, x_u, y_u, mask, sizes, plan.assign, hcfg,
                      x_test=ds.x_test, y_test=ds.y_test,
                      participate_fn=participate, ckpt_manager=mgr,
                      start_iter=start)
    wall = time.time() - t0

    # ---- 5. report -----------------------------------------------------
    report = {
        "dataset": args.dataset,
        "acc": hist["acc"],
        "final_acc": hist["acc"][-1] if hist["acc"] else None,
        "objective_R": float(plan.R),
        "energy_J": float(cb.E_sum),
        "delay_s": float(cb.T_sum),
        "train_wall_s": round(wall, 1),
        "global_iters": args.iters - start,
    }
    print("[result] " + json.dumps(report))
    return report


if __name__ == "__main__":
    main()
