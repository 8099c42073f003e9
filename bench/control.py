#!/usr/bin/env python3
"""The control of the comparison: the plain reference in the program's place,
one precision below the configuration's (bfloat16 for float32).

    python3 bench/control.py --workload metro.churn --seconds 20 \
        --seeds 101 202 303

For each seed the cell runs a short window as ``run_cell.py`` does; then the
same sampled inputs are answered twice, once by the program (its plans and
search trajectories, as a run reads them) and once by the reference in
bfloat16: re-pricing by its SROA, re-search by its own descent with the
engine's rules (steepest single move, or joint move where the plans carry
compression levels; Definition 1/2 escapes; stop on a revisit).  Both are
held to ``check.py``'s comparison in float32.  One JSON line per seed; the
control has to come out not correct.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import copy
import functools
import gc
import json
import os
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
CONTROL_DTYPE = "bfloat16"


@functools.lru_cache(maxsize=None)
def _kernels(caps: tuple, dtype_name: str, ladder: tuple | None = None):
    import jax
    import jax.numpy as jnp
    from bench import reference as ref
    dt = jnp.dtype(dtype_name)

    def plan(cell, assign, mask, lam, comp):
        c = ref.cast(cell, dt)
        b, f, p, t, R, _ = ref.sroa(c, assign, mask, jnp.asarray(lam, dt),
                                    caps, comp, ladder)
        R_m = ref.evaluate(c, assign, b, f, p, jnp.asarray(lam, dt), mask,
                           comp, ladder)[1]
        return b, f, p, t, R, R_m

    def nbhd(cell, assign, mask, lam, comp):
        return ref.score_neighbourhood(ref.cast(cell, dt), assign, mask,
                                       jnp.asarray(lam, dt), caps, comp,
                                       ladder)[1]

    return jax.jit(plan), jax.jit(nbhd)


def reprice(item: dict, caps: tuple, ladder: tuple | None = None,
            dtype: str = CONTROL_DTYPE) -> dict:
    """The item with its plan answered by the reference in ``dtype``, at
    the item's deployed levels where it has them."""
    plan, _ = _kernels(caps, dtype, ladder)
    b, f, p, t, R, _ = plan(item["cell"], item["assign"], item["mask"],
                            np.float32(item["lam"]), item.get("comp"))
    out = dict(item)
    out.update({k: np.asarray(v, np.float32)
                for k, v in zip("bfptR", (b, f, p, t, R))})
    return out


def _decode(j: int, cur, lv, M: int, L: int):
    """Row ``j`` > 0 of the (joint) neighbourhood of (cur, lv) as a move:
    (assignment, levels, user, src, dst, kind)."""
    N = cur.shape[0]
    cur, lv = cur.copy(), None if lv is None else lv.copy()
    if j <= N * (M - 1):
        (u, k), state, n, kind = divmod(j - 1, M - 1), cur, M, 0
    else:
        (u, k), state, n, kind = divmod(j - 1 - N * (M - 1), L - 1), lv, L, 2
    src = int(state[u])
    state[u] = (src + k + 1) % n
    return cur, lv, u, src, int(state[u]), kind


def search(item: dict, caps: tuple, max_rounds: int, escape_iters: int,
           ladder: tuple | None = None, dtype: str = CONTROL_DTYPE) -> dict:
    """The item with its search run by the reference in ``dtype``, recorded
    as the engine records its trajectory (user, src, dst, kind, moved).
    Where the item has levels, the walk is over (assignment, levels) pairs
    through the joint neighbourhood, and an escape keeps the levels."""
    plan, nbhd = _kernels(caps, dtype, ladder)
    cell, mask = item["cell"], item["mask"]
    lam = np.float32(item["lam"])
    M = cell["gain"].shape[1]
    cur = item["init"].astype(np.int32).copy()
    lv = item.get("init_comp")
    lv = None if lv is None else lv.astype(np.int32).copy()
    L = 1 if lv is None else len(ladder)
    moves = np.zeros((max_rounds, 5), np.int32)
    valid = np.zeros(max_rounds, bool)
    key = lambda a, c: (a.tobytes(), None if c is None else c.tobytes())  # noqa: E731
    visited = {key(cur, lv)}
    best_R, best, best_lv = np.inf, cur.copy(), lv
    escapes = 0
    for r in range(max_rounds):
        R = np.asarray(nbhd(cell, cur, mask, lam, lv), np.float64)
        j = int(np.argmin(R))
        valid[r] = True
        nxt, nxt_lv, u, src, dst, kind = (_decode(j, cur, lv, M, L) if j
                                          else (cur, lv, 0, 0, 0, 0))
        if R[j] < best_R:
            best_R, best, best_lv = R[j], nxt, nxt_lv
        if not R[j] < R[0]:
            b, _, _, _, _, R_m = (np.asarray(x) for x in
                                  plan(cell, cur, mask, lam, lv))
            occ = np.bincount(cur[mask], minlength=M) > 0
            m_plus = int(np.argmax(np.where(occ, R_m, -np.inf)))
            m_minus = int(np.argmin(R_m))
            member = (cur == m_plus) & mask
            if m_plus == m_minus or not member.any() or escapes >= escape_iters:
                moves[r] = (0, 0, 0, 1, 0)
                break
            u = int(np.argmax(np.where(member, b, -np.inf)))
            src, dst, kind = int(cur[u]), m_minus, 1
            nxt, nxt_lv = cur.copy(), lv
            nxt[u] = dst
            escapes += 1
        moves[r] = (u, src, dst, kind, 1)
        cur, lv = nxt, nxt_lv
        if key(cur, lv) in visited:
            break
        visited.add(key(cur, lv))
    out = dict(item)
    out.update(moves=moves, valid=valid, served=best)
    if best_lv is not None:
        out["served_comp"] = best_lv
    return out


def answered_by_control(gathered: dict, caps: tuple, max_rounds: int,
                        escape_iters: int, ladder: tuple | None = None) -> dict:
    out = dict(gathered)
    out["reprice"] = [reprice(it, caps, ladder) for it in gathered["reprice"]]
    out["search"] = [search(it, caps, max_rounds, escape_iters, ladder)
                     for it in gathered["search"]]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import check, harness
    from repro.runtime import compile_cache

    cell = harness.resolve(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("[control] needs a TPU", file=sys.stderr)
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compile_cache.enable()
    clock = harness.CompileClock()
    sroa, svc = cell.config["sroa"], cell.config["service"]
    caps = (sroa["b_iters"], sroa["f_iters"], sroa["p_iters"],
            sroa["t_iters"])
    ladder = harness.ladder_factors(cell.config)
    for seed in args.seeds:
        service = harness.build(cell, devices[:cell.chips])
        harness.warm(service, cell.traffic["warm_share"])
        run = harness.Run(cell=copy.copy(cell), seed=seed,
                          seconds=args.seconds)
        harness.drive(service, run, harness.Spans(), clock)
        g = check.gather(run)
        run.release()
        del service
        gc.collect()
        prog = check.compare(g, caps, ladder)
        ctrl = check.compare(answered_by_control(
            g, caps, svc["max_rounds"], svc["escape_iters"], ladder), caps,
            ladder)
        print(json.dumps({"seed": seed, "ticks": len(run.ticks),
                          "program": prog, "control": ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
