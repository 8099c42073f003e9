#!/usr/bin/env python3
"""Smoke run of the planning service on a TPU, end to end.

    python chip_smoke.py              # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4    # four chips: sharded re-search parity

One chip.  The fleet and the control plane are built exactly as
``python -m repro.launch.serve --mode plan`` builds them (``service_setup``
-> ``PlanningService`` -> ``run_load``), at paper width: C=128 cells of up
to N=50 users and M=5 edges (PAPER.md §VI-A), drawn from ``--seed`` and
pinned to one device.

  (a) served path, default solver route: cold bootstrap, then 4 ticks of
      Poisson plan requests.  Summed R is finite, every request is
      answered, infeasible cells are counted.
  (b) reference: for 4 seed-chosen cells the deployed assignment is
      re-solved by the plain ``repro.core.sroa.solve`` on the same device;
      its R matches the service's re-priced R to rtol 1e-4.
  (c) kernel route: the same fleet is re-searched with the fused SROA
      kernel and top-k move pruning.  The compiled program holds both
      Mosaic kernels (``sroa_solve``, ``topk_moves``) and its summed R is
      within 1% of (a).

Four chips (``--chips 4``, nothing else runs).  ``solve_fleet_sharded``
(kernel route of phase (c)) over a 4-device cell mesh for C=512 paper
cells, against the same fleet with ``mesh=None`` on one of those devices:
assignments bitwise equal, R to rtol 1e-5, and the sharded result placed
on all four devices.

The script refuses any platform but the TPU: the CPU rehearsal runs the
phase functions through ``tests/test_chip_smoke.py``.  Timings are a smoke
run's, not a benchmark's.  The last line of standard output is the JSON
verdict ``{"ok": true, "device": {...}}``; any failed check exits non-zero
before it is printed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SMOKE = "[smoke run, not a benchmark]"

PAPER_USERS, PAPER_EDGES = 50, 5
ONE_CHIP_CELLS, FOUR_CHIP_CELLS = 128, 512
TICKS, REQ_PER_TICK, TOP_K, REF_CELLS = 4, 2.0, 8, 4


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _import_repro() -> None:
    """Import the package from this checkout's ``src/`` and nowhere else."""
    src = ROOT / "src"
    sys.path.insert(0, str(src))
    try:
        import repro
    except ImportError as e:
        raise SmokeFailure(f"no repro package under {src}: {e}") from e
    where = [Path(p).resolve() for p in repro.__path__]
    _check(where == [src / "repro"], f"repro imported from {where}, not {src}")


class CompileClock:
    """Seconds spent tracing, lowering and compiling, plus persistent-cache
    hits and misses, from JAX's own monitoring events."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, duration, **_):
        if event in self._DURATIONS:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return self.seconds, self.hits, self.misses

    def since(self, mark) -> dict:
        s, h, m = mark
        return {"compile_s": self.seconds - s, "cache_hits": self.hits - h,
                "cache_misses": self.misses - m}


def _log(tag: str, **fields) -> None:
    print(f"[chip_smoke] {tag} {SMOKE} {json.dumps(fields)}", flush=True)


def _serve_args(cells: int, users: int, edges: int, seed: int):
    """The ``--mode plan`` CLI namespace for this fleet size."""
    from repro.fleet.service import ServiceConfig
    from repro.launch import serve
    return serve.parser().parse_args([
        "--mode", "plan", "--cells", str(cells), "--cell-users", str(users),
        "--cell-edges", str(edges), "--rounds", str(TICKS),
        "--req-rate", str(REQ_PER_TICK),
        "--event-rate", str(ServiceConfig().event_rate),
        "--seed", str(seed)])


def _active_cell(svc, i: int):
    """Cell i of the live fleet with only its active users, and their edges."""
    import jax
    import numpy as np
    from repro.fleet.batch import _PER_USER_FIELDS

    row = jax.tree.map(lambda x: x[i], svc.fleet.cells)
    users = np.flatnonzero(np.asarray(svc.state.active[i], bool))
    cut = {f: getattr(row, f)[users] for f in _PER_USER_FIELDS}
    return row._replace(**cut), svc.assigns[i][users]


def run_phases(cells: int, users: int, edges: int, seed: int, devices,
               clock: CompileClock) -> dict:
    """Phases (a)-(c) on ``devices[0]``; raises SmokeFailure on any miss."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import sroa
    from repro.fleet import engine as fengine
    from repro.fleet.service import PlanningService, run_load
    from repro.kernels import ops
    from repro.launch import serve

    dev = devices[0]
    args = _serve_args(cells, users, edges, seed)
    spec, fleet, cfg, svc_cfg = serve.service_setup(args)
    _check(fleet.N_max == users and fleet.M == edges,
           f"fleet drew N_max={fleet.N_max}, M={fleet.M}")

    # ---- (a) served path, default solver route ---------------------------
    mark, t0 = clock.mark(), time.perf_counter()
    svc = PlanningService(fleet, lam=args.lam, sroa_cfg=cfg, cfg=svc_cfg,
                          spec=spec, seed=args.seed, devices=[dev])
    boot_s = time.perf_counter() - t0
    _check(svc.mesh is None, "service sharded over more than one device")
    _log("a/bootstrap", cells=fleet.C, n_max=fleet.N_max, m=fleet.M,
         bootstrap_s=boot_s, sum_R=float(svc.R_ref.sum()),
         **clock.since(mark))

    ticks = []

    def on_tick(rec):
        ticks.append(rec)
        _log("a/tick", tick=rec.tick, tick_ms=rec.tick_ms, sum_R=rec.sum_R,
             changed=rec.changed, replanned=int(rec.replanned.size),
             served=rec.served)

    mark = clock.mark()
    snap = run_load(svc, ticks=args.rounds, req_per_tick=args.req_rate,
                    seed=args.seed + 7, on_tick=on_tick)
    sum_R_a = float(np.asarray(svc.alloc.R, np.float64).sum())
    infeasible = np.flatnonzero(~np.asarray(svc.alloc.feasible, bool))
    _log("a/ticks", ticks=len(ticks), requests=svc.telemetry.requests,
         served=snap["requests_served"], unserved=snap["unserved"],
         sum_R=sum_R_a, infeasible_cells=infeasible.tolist(),
         **clock.since(mark))
    _check(len(ticks) == args.rounds, f"ran {len(ticks)} ticks")
    _check(np.isfinite(sum_R_a), f"summed R is {sum_R_a}")
    _check(snap["unserved"] == 0
           and snap["requests_served"] == svc.telemetry.requests,
           f"{snap['unserved']} unserved of {svc.telemetry.requests}")

    # ---- (b) reference check against the plain solver --------------------
    picks = np.random.default_rng(seed).choice(
        fleet.C, size=min(REF_CELLS, fleet.C), replace=False)
    ref = []
    for i in picks:
        scn, assign = _active_cell(svc, int(i))
        with jax.default_device(dev):
            res = sroa.solve(scn, jnp.asarray(assign, jnp.int32), svc.lam,
                             svc.sroa_cfg)
        ref.append((int(i), float(res.R), float(svc.alloc.R[i])))
    _log("b/reference", cells=[r[0] for r in ref],
         R_plain=[r[1] for r in ref], R_service=[r[2] for r in ref])
    for i, want, got in ref:
        _check(np.isclose(got, want, rtol=1e-4, atol=0.0),
               f"cell {i}: service R {got} vs plain solve {want}")

    # ---- (c) kernel route: fused SROA + top-k pruning --------------------
    kcfg = dataclasses.replace(svc.sroa_cfg, fused=True)
    init = jax.device_put(jnp.asarray(svc.assigns, jnp.int32), dev)
    live = jax.device_put(svc.fleet, dev)
    mark, t0 = clock.mark(), time.perf_counter()
    compiled = fengine.solve_fleet_assignments.lower(
        live, init, svc.lam, cfg=kcfg, max_rounds=svc.cfg.max_rounds,
        escape_iters=svc.cfg.escape_iters, top_k=TOP_K).compile()
    compile_s = time.perf_counter() - t0
    kernels = sorted(ops.compiled_kernel_names(compiled.as_text()))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(live, init, svc.lam))
    run_s = time.perf_counter() - t0
    sum_R_c = float(np.asarray(out.R, np.float64).sum())
    _log("c/kernels", kernels=kernels, compile_wall_s=compile_s,
         search_s=run_s, sum_R=sum_R_c, sum_R_a=sum_R_a,
         ratio=sum_R_c / sum_R_a, **clock.since(mark))
    if dev.platform == "tpu":
        _check({"sroa_solve", "topk_moves"} <= set(kernels),
               f"compiled kernel route holds {kernels}")
    _check(np.isfinite(sum_R_c) and sum_R_c <= 1.01 * sum_R_a,
           f"kernel-route summed R {sum_R_c} vs served {sum_R_a}")
    return {"sum_R_a": sum_R_a, "sum_R_c": sum_R_c, "kernels": kernels,
            "infeasible": infeasible.tolist(), "reference": ref}


def run_sharded_parity(cells: int, users: int, edges: int, seed: int,
                       devices, clock: CompileClock) -> dict:
    """Sharded re-search over ``devices`` == the same search on one device.

    Both searches take the kernel route of phase (c) (fused SROA + top-k):
    the jnp route's 201-candidate rounds would cost the single-device
    reference about four one-chip bootstraps at C=512.
    """
    import jax
    import numpy as np
    from repro.fleet.service import solve_fleet_sharded
    from repro.launch import serve
    from repro.runtime.sharding import cell_mesh

    args = _serve_args(cells, users, edges, seed)
    _, fleet, cfg, svc_cfg = serve.service_setup(args)
    kw = dict(lam=args.lam, cfg=dataclasses.replace(cfg, fused=True),
              max_rounds=svc_cfg.max_rounds,
              escape_iters=svc_cfg.escape_iters, top_k=TOP_K)
    mesh = cell_mesh(devices)
    _check(mesh is not None and mesh.devices.size == len(devices),
           f"no {len(devices)}-device cell mesh")

    mark, t0 = clock.mark(), time.perf_counter()
    got = jax.block_until_ready(solve_fleet_sharded(fleet, mesh=mesh, **kw))
    sharded_s = time.perf_counter() - t0
    placed = {s.device for s in got.assign.addressable_shards}
    rows = sorted(s.data.shape[0] for s in got.assign.addressable_shards)
    _log("4chip/sharded", cells=fleet.C, devices=len(devices),
         shard_rows=rows, wall_s=sharded_s, **clock.since(mark))
    _check(placed == set(devices),
           f"sharded assignment lives on {len(placed)} of {len(devices)}")

    mark, t0 = clock.mark(), time.perf_counter()
    one = jax.device_put(fleet, devices[0])
    want = jax.block_until_ready(solve_fleet_sharded(one, mesh=None, **kw))
    single_s = time.perf_counter() - t0
    _log("4chip/single", cells=fleet.C, wall_s=single_s,
         device=str(next(iter(want.assign.devices()))), **clock.since(mark))
    _check(want.assign.devices() == {devices[0]},
           "single-device search left its device")

    a_got, a_want = np.asarray(got.assign), np.asarray(want.assign)
    R_got, R_want = np.asarray(got.R), np.asarray(want.R)
    mismatched = int((a_got != a_want).any(axis=1).sum())
    _log("4chip/parity", cells_with_other_assign=mismatched,
         max_rel_dR=float(np.max(np.abs(R_got - R_want)
                                 / np.maximum(np.abs(R_want), 1e-30))),
         sum_R=float(R_got.sum()))
    _check(mismatched == 0, f"{mismatched} cells assigned differently")
    _check(np.allclose(R_got, R_want, rtol=1e-5, atol=0.0),
           "sharded R differs beyond rtol 1e-5")
    return {"sum_R": float(R_got.sum()), "mismatched": mismatched}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        _import_repro()
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    import jax
    from repro.runtime import compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] FAILED: needs a TPU, JAX found "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[chip_smoke] FAILED: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()
    clock = CompileClock()
    _log("device", platform=dev.platform, device_kind=dev.device_kind,
         count=len(devices), jax=jax.__version__, cache_dir=cache_dir,
         date=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_sharded_parity(FOUR_CHIP_CELLS, PAPER_USERS, PAPER_EDGES,
                               args.seed, devices[:4], clock)
        else:
            run_phases(ONE_CHIP_CELLS, PAPER_USERS, PAPER_EDGES, args.seed,
                       devices[:1], clock)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    _log("done", wall_s=time.perf_counter() - t0, compile_s=clock.seconds,
         cache_hits=clock.hits, cache_misses=clock.misses)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
