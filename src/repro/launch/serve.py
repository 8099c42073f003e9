"""Batched serving drivers: LM decode and fleet planning.

``--mode lm`` (default) demonstrates the LM serve path end-to-end on CPU
with a reduced config of any assigned arch (the full configs are exercised
by the dry-run):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --batch 4 --prompt-len 32 --new-tokens 16

``--mode plan`` serves the fleet planning endpoint as a streaming control
plane (:mod:`repro.fleet.service`): a clocked loop advances scenario
dynamics (mobility / fading / churn) for the whole fleet each tick,
re-prices every cached plan under the new channel, re-searches only the
cells past the drift threshold, and answers the tick's (coalesced)
Poisson request load from the plan table:

  PYTHONPATH=src python -m repro.launch.serve --mode plan \
      --cells 8 --rounds 3 --cell-users 12 --cell-edges 3

``--no-stream`` keeps the pre-service request/response loop (per-cell
``FleetPlanner.plan`` calls with warm starts) for parity checks;
``--replan-all`` turns off drift gating (the re-search-everything
baseline the benchmark compares against).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import compile_cache


def plan_request(planner, scn, warm_assign=None, new_users=None,
                 mask=None) -> dict:
    """One planning request -> JSON-able response (the endpoint contract)."""
    plan = planner.plan(scn, warm_assign=warm_assign, new_users=new_users,
                        mask=mask)
    return {
        "assign": plan.assign.tolist(),
        "b_hz": plan.b.tolist(),
        "f_hz": plan.f.tolist(),
        "p_w": plan.p.tolist(),
        "objective": plan.R,
        "deadline_s": plan.t,
        "cached": plan.cached,
        "solve_calls": plan.solve_calls,
        "plan_ms": plan.plan_ms,
    }


def _parse_tiers(s: str) -> tuple:
    """``--tiers`` grammar: comma-separated rungs of
    ``name[:cycle_mult[:size_mult[:f_scale[:prob]]]]`` — omitted fields
    default to 1.0 (e.g. ``lo:1.5:1.0:0.6:0.3,mid,hi:0.7:1.2:1.4:0.3``)."""
    from repro.core.wireless import DeviceTier

    tiers = []
    for part in s.split(","):
        fields = part.strip().split(":")
        vals = [float(x) for x in fields[1:]]
        kw = dict(zip(("cycle_mult", "size_mult", "f_scale", "prob"), vals))
        tiers.append(DeviceTier(fields[0], **kw))
    return tuple(tiers)


def _serve_ladder(args):
    if not args.compression:
        return None
    from repro.fed.compression import default_ladder
    return default_ladder(args.topk_frac)


def _draw_serve_fleet(args):
    from repro.core import sroa
    from repro.core.wireless import ScenarioSpec
    from repro.fleet import draw_fleet

    # Topology mode (D12): draw M_cand candidate sites per cell but open
    # only --cell-edges of them; the service's periodic redesign decides
    # which (and how many) stay open.
    m_cand = max(args.m_cand, args.cell_edges)
    spec = dataclasses.replace(ScenarioSpec(), N=args.cell_users,
                               M=m_cand,
                               tiers=_parse_tiers(args.tiers)
                               if args.tiers else ())
    n_lo = min(max(4, args.cell_users // 2), args.cell_users)
    fleet = draw_fleet(args.seed, args.cells, spec,
                       n_range=(n_lo, args.cell_users))
    if m_cand > args.cell_edges or args.topology_period:
        from repro.fleet import topology as ftopo
        fleet = ftopo.with_edge_mask(
            fleet, ftopo.uniform_mask(fleet.C, m_cand, args.cell_edges))
    cfg = sroa.SroaConfig(b_iters=30, f_iters=24, p_iters=20, t_iters=28)
    return spec, fleet, cfg


def service_setup(args):
    """(spec, fleet, SroaConfig, ServiceConfig) of the ``--mode plan``
    control plane, drawn from ``--seed`` and the planning CLI knobs."""
    from repro.fleet.service import DriftConfig, ServiceConfig

    spec, fleet, cfg = _draw_serve_fleet(args)
    topo = None
    if args.topology_period:
        from repro.fleet.topology import TopologyConfig
        topo = TopologyConfig(edge_cost=args.edge_cost)
    svc_cfg = ServiceConfig(
        drift=DriftConfig(channel_threshold=args.drift_threshold,
                          objective_threshold=args.obj_threshold),
        event_rate=args.event_rate, replan_all=args.replan_all,
        max_rounds=args.plan_rounds, escape_iters=2,
        top_k=args.top_k, n_starts=args.n_starts,
        horizon=args.horizon, switch_cost=args.switch_cost,
        ladder=_serve_ladder(args), topology_period=args.topology_period,
        topology=topo)
    return spec, fleet, cfg, svc_cfg


def run_service(args) -> dict:
    """Run the ``--mode plan`` streaming control plane."""
    import json

    from repro.fleet.service import PlanningService, run_load

    spec, fleet, cfg, svc_cfg = service_setup(args)
    ladder = svc_cfg.ladder
    mode = "replan-all" if args.replan_all else "drift-gated"
    if args.horizon > 1 or args.switch_cost:
        mode += (f", horizon K={args.horizon}"
                 f" switch_cost={args.switch_cost:g}")
    if args.tiers:
        mode += f", {len(spec.tiers)} device tiers"
    if ladder is not None:
        mode += f", compression ladder ({len(ladder)} rungs)"
    if args.topology_period:
        mode += (f", topology redesign every {args.topology_period} ticks "
                 f"(M_cand={fleet.M}, edge_cost={args.edge_cost:g})")
    print(f"[serve] fleet: {fleet.C} cells, N_max={fleet.N_max}, "
          f"M={fleet.M} (streaming control plane, {mode})")
    t0 = time.time()
    svc = PlanningService(fleet, lam=args.lam, sroa_cfg=cfg, cfg=svc_cfg,
                          spec=spec, seed=args.seed)
    print(f"[serve] bootstrap: sum R={float(svc.R_ref.sum()):.1f} "
          f"in {time.time() - t0:.2f}s")

    def on_tick(rec):
        topo = (f", {rec.topo_moves} topo moves" if rec.topo_moves else "")
        print(f"[serve] tick {rec.tick}: {rec.changed} changed, "
              f"{rec.replanned.size} replanned, {rec.served} served "
              f"(coalesced {rec.coalesced}), sum R={rec.sum_R:.1f}, "
              f"{rec.tick_ms:.0f}ms{topo}")

    snap = run_load(svc, ticks=args.rounds, req_per_tick=args.req_rate,
                    seed=args.seed + 7, on_tick=on_tick)
    print(f"[serve] telemetry: {json.dumps(snap)}")
    return {"sum_R": snap["objective_sum"] / max(snap["ticks"], 1),
            "stats": snap}


def run_planner(args) -> dict:
    """The ``--no-stream`` driver: per-cell request loop (pre-service)."""
    from repro.fleet import FleetPlanner
    from repro.fleet import dynamics

    spec, fleet, cfg = _draw_serve_fleet(args)
    planner = FleetPlanner(lam=args.lam, cfg=cfg,
                           max_rounds=args.plan_rounds, escape_iters=2,
                           use_engine=not args.host_loop,
                           top_k=args.top_k, n_starts=args.n_starts,
                           ladder=_serve_ladder(args))

    route = "host loop" if args.host_loop else "device-resident engine"
    print(f"[plan] fleet: {fleet.C} cells, N_max={fleet.N_max}, "
          f"M={fleet.M} (route: {route})")
    t0 = time.time()
    plans = planner.plan_fleet(fleet)
    total_R = sum(p.R for p in plans)
    print(f"[plan] cold round: sum R={total_R:.1f} in {time.time()-t0:.2f}s "
          f"({sum(p.solve_calls for p in plans)} batched solves)")

    cells = [fleet.cell(i) for i in range(fleet.C)]
    states = [dynamics.init_state(c, seed=args.seed + i)
              for i, c in enumerate(cells)]
    warm = [p.assign for p in plans]
    rng = np.random.default_rng(args.seed)
    for rnd in range(args.rounds):
        # A random subset of cells sees a dynamics event; the rest are
        # unchanged and must come back as cache hits.
        moved = rng.uniform(size=fleet.C) < args.event_rate
        events = [None] * fleet.C
        for i in np.flatnonzero(moved):
            cells[i], states[i] = dynamics.mobility_step(
                cells[i], states[i], rng)
            cells[i], states[i], events[i] = dynamics.churn_step(
                cells[i], states[i], rng, spec)
        t0 = time.time()
        responses = [
            plan_request(planner, cells[i],
                         warm_assign=warm[i],
                         new_users=None if events[i] is None
                         else events[i].arrived,
                         mask=states[i].active)
            for i in range(fleet.C)
        ]
        # Each round's assignments seed the next round's warm starts.
        warm = [np.asarray(r["assign"], np.int32) for r in responses]
        dt = time.time() - t0
        hits = sum(r["cached"] for r in responses)
        total_R = sum(r["objective"] for r in responses)
        print(f"[plan] round {rnd}: {int(moved.sum())} cells changed, "
              f"{hits}/{fleet.C} cache hits, sum R={total_R:.1f}, "
              f"{dt*1e3:.0f}ms")
    print(f"[plan] cache stats: {planner.stats}")
    return {"sum_R": total_R, "stats": planner.stats}


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="lm", choices=("lm", "plan"))
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (TPU-scale; default reduced)")
    # planning endpoint knobs
    ap.add_argument("--cells", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--cell-users", type=int, default=12)
    ap.add_argument("--cell-edges", type=int, default=3)
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="engine move pruning: score only the k "
                         "kernel-nominated moves per round (0 = full "
                         "neighbourhood)")
    ap.add_argument("--n-starts", type=int, default=1,
                    help="engine multi-start restarts per search")
    ap.add_argument("--horizon", type=int, default=1,
                    help="rolling-horizon slots per plan: score candidates "
                         "against K predicted channel slots (1 = snapshot "
                         "planning; D10)")
    ap.add_argument("--switch-cost", type=float, default=0.0,
                    help="weighted-cost charge per handover off the "
                         "deployed assignment (rolling-horizon mode)")
    ap.add_argument("--topology-period", type=int, default=0,
                    help="streaming mode: redesign edge placement/"
                         "activation every P ticks (0 = fixed topology; "
                         "D12)")
    ap.add_argument("--edge-cost", type=float, default=0.0,
                    help="weighted-cost charge per OPEN edge site in the "
                         "topology design objective (D12)")
    ap.add_argument("--m-cand", type=int, default=0,
                    help="candidate edge sites per cell; --cell-edges of "
                         "them start open and the redesign may relocate "
                         "activation among all of them (0 = no candidate "
                         "pool: M = --cell-edges)")
    ap.add_argument("--tiers", default="",
                    help="device tiers, comma-separated "
                         "name[:cycle_mult[:size_mult[:f_scale[:prob]]]] "
                         "rungs (e.g. 'lo:1.5:1.0:0.6:0.3,mid,"
                         "hi:0.7:1.2:1.4:0.3'); empty = homogeneous (D11)")
    ap.add_argument("--compression", action="store_true",
                    help="optimize per-user upload compression jointly "
                         "with assignment (none/int8/top-k ladder; D11)")
    ap.add_argument("--topk-frac", type=float, default=0.05,
                    help="top-k sparsification fraction of the ladder's "
                         "highest rung (with --compression)")
    ap.add_argument("--plan-rounds", type=int, default=12,
                    help="batched-TSIA iteration budget per cold plan")
    ap.add_argument("--event-rate", type=float, default=0.4,
                    help="per-round probability a cell sees dynamics")
    ap.add_argument("--host-loop", action="store_true",
                    help="plan via the PR 1 host-driven loop instead of "
                         "the device-resident engine (implies --no-stream)")
    ap.add_argument("--no-stream", action="store_true",
                    help="serve via the pre-service per-cell request loop "
                         "instead of the streaming control plane")
    ap.add_argument("--replan-all", action="store_true",
                    help="streaming mode: disable drift gating (re-search "
                         "every cell every tick — the bench baseline)")
    ap.add_argument("--drift-threshold", type=float, default=0.25,
                    help="channel-drift replan threshold (relative)")
    ap.add_argument("--obj-threshold", type=float, default=0.02,
                    help="objective-degradation replan threshold")
    ap.add_argument("--req-rate", type=float, default=2.0,
                    help="streaming mode: Poisson plan requests per tick")
    return ap


def main(argv=None):
    args = parser().parse_args(argv)
    compile_cache.enable()

    if args.mode == "plan":
        if args.no_stream or args.host_loop:
            return run_planner(args)
        return run_service(args)

    from repro import configs
    from repro.models import transformer as tf

    if args.arch not in configs.ARCHS:
        raise SystemExit(f"unknown arch {args.arch!r}")
    cfg = configs.get(args.arch)
    if not cfg.has_decode:
        raise SystemExit(f"{args.arch} is encoder-only (no decode)")
    if not args.full_size:
        cfg = cfg.reduced()
    print(f"[serve] arch={args.arch} family={cfg.family} "
          f"layers={cfg.n_layers} d={cfg.d_model}")

    key = jax.random.PRNGKey(args.seed)
    params = tf.init_params(cfg, key)
    B, T = args.batch, args.prompt_len
    prompts = jax.random.randint(key, (B, T), 0, cfg.vocab)

    prefill = jax.jit(tf.make_prefill_step(cfg))
    serve = jax.jit(tf.make_serve_step(cfg))

    t0 = time.time()
    logits, cache = prefill(params, {"tokens": prompts})
    logits.block_until_ready()
    t_prefill = time.time() - t0
    print(f"[prefill] {B}x{T} tokens in {t_prefill:.2f}s "
          f"(incl. compile)")

    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out_tokens = [np.asarray(tok)]
    t0 = time.time()
    for i in range(args.new_tokens):
        logits, cache = serve(params, cache, tok)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out_tokens.append(np.asarray(tok))
    jax.block_until_ready(tok)
    dt = time.time() - t0
    tps = args.new_tokens * B / dt
    gen = np.concatenate(out_tokens, 1)
    print(f"[decode] {args.new_tokens} steps x batch {B} in {dt:.2f}s "
          f"-> {tps:.1f} tok/s ({jax.devices()[0].platform}, "
          f"incl. compile)")
    print(f"[sample] first sequence: {gen[0][:16].tolist()}")
    return {"tok_per_s": tps, "prefill_s": t_prefill}


if __name__ == "__main__":
    main()
