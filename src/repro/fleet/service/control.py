"""The planning control plane: a clocked loop that owns a live fleet.

:class:`PlanningService` turns the fleet engine's "one fast jitted call"
into a streaming system.  Each :meth:`tick`:

1. **advances dynamics** for the whole fleet in one batched step
   (:func:`repro.fleet.dynamics.fleet_step` — mobility / block fading /
   churn; unchanged cells stay bit-identical);
2. **re-prices** every cell's cached assignment under the new channel with
   ONE batched SROA call (`FleetPlanner.allocate_fleet` — the cheap data
   plane), so every response always carries a current b/f/p allocation;
3. **scores drift** (:mod:`repro.fleet.service.drift`) and re-searches
   assignments ONLY for cells past a replan threshold (plus churn
   arrivals), warm-started from the cached plans, batched as a sliced
   sub-fleet through the device-resident engine — sharded over devices
   when more than one is visible (:mod:`repro.fleet.service.shard`).
   Replan sets are padded to power-of-two buckets so the engine compiles
   O(log C) programs, not one per subset size;
4. **serves** every queued request with the tick's plan snapshot —
   concurrent requests coalesce into that single engine call
   (:mod:`repro.fleet.service.queue`).

Telemetry (plans/sec, replan fraction, latency percentiles, drift
histogram) accumulates in :mod:`repro.fleet.service.telemetry`.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sroa
from repro.core.wireless import Scenario, ScenarioSpec
from repro.fleet import batch as fbatch
from repro.fleet import dynamics
from repro.fleet import engine as fengine
from repro.fleet.planner import FleetPlanner, PlanResult, scenario_digest
from repro.fleet.service import drift as fdrift
from repro.fleet.service import shard as fshard
from repro.fleet.service.queue import CoalescingQueue, PlanRequest
from repro.fleet.service.telemetry import Telemetry


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Control-plane knobs (solver knobs live on the FleetPlanner)."""

    drift: fdrift.DriftConfig = fdrift.DriftConfig()
    stream: dynamics.StreamConfig = dynamics.StreamConfig()
    event_rate: float = 1.0    # fraction of cells advanced per tick
    replan_all: bool = False   # baseline: re-search every cell every tick
    max_rounds: int = 12       # engine budget per re-search
    escape_iters: int = 2
    warm_start: bool = True    # seed re-searches from the cached plans
    bucket: bool = True        # pad replan sets to power-of-two buckets
    shard: bool = True         # shard the cell axis over visible devices
    top_k: int = 0             # engine move pruning (0 = full nbhd; D9)
    n_starts: int = 1          # engine multi-start restarts (D9)
    horizon: int = 1           # predicted slots per plan (1 = snapshot; D10)
    switch_cost: float = 0.0   # weighted-cost charge per handover (D10)
    ladder: object = None      # CompressionLadder: >= 2 rungs makes
    #                            per-user compression a decision var (D11)
    topology_period: int = 0   # redesign the edge topology every P ticks
    #                            (0 = off; needs a fleet with an edge_mask
    #                            — the slow timescale of D12)
    topology: object = None    # TopologyConfig for the redesign (None =
    #                            defaults; edge_cost lives here)


class TickRecord(NamedTuple):
    tick: int
    changed: int               # cells that saw dynamics this tick
    replanned: np.ndarray      # cell indices re-searched this tick
    engine_calls: int          # assignment-search calls spent (0 or 1)
    sum_R: float               # repriced objective summed over cells
    served: int                # requests answered this tick
    coalesced: int             # largest request group sharing the call
    tick_ms: float
    drift: fdrift.DriftReport | None
    handovers: int = 0         # active users whose edge changed this tick
    topo_moves: int = 0        # topology moves accepted this tick (D12)


class PlanningService:
    """Streaming planning endpoint over one live fleet."""

    def __init__(self, fleet: fbatch.FleetScenario, lam: float = 1.0,
                 sroa_cfg: sroa.SroaConfig | None = None,
                 cfg: ServiceConfig = ServiceConfig(),
                 planner: FleetPlanner | None = None,
                 spec: ScenarioSpec | None = None, seed: int = 0,
                 devices=None):
        self.cfg = cfg
        self.spec = spec or ScenarioSpec()
        self.planner = planner or FleetPlanner(
            lam=lam, cfg=sroa_cfg or sroa.SroaConfig(),
            max_rounds=cfg.max_rounds, escape_iters=cfg.escape_iters,
            top_k=cfg.top_k, n_starts=cfg.n_starts, ladder=cfg.ladder)
        self.lam = self.planner.lam
        self.sroa_cfg = self.planner.cfg
        # An explicit planner wins: its ladder is the one every solve uses.
        self.ladder = self.planner.ladder
        self._comp_on = fengine._comp_enabled(self.ladder)
        self.mesh = fshard.cell_mesh(devices) if cfg.shard else None
        self.state = dynamics.init_fleet_state(
            fleet, seed=seed, mean_speed=cfg.stream.mean_speed)
        self.fleet = fleet._replace(mask=jnp.asarray(self.state.active))
        self.rng = np.random.default_rng(seed + 1)
        self.queue = CoalescingQueue()
        self.telemetry = Telemetry()
        self.tick_idx = 0
        with self.telemetry.span("svc.bootstrap"):
            self._bootstrap()

    # -------------------------------------------------------------- engine
    def _horizon_mode(self) -> bool:
        return self.cfg.horizon > 1 or self.cfg.switch_cost != 0.0

    def _engine(self, fleet, init_assigns, rows=None, init_comps=None,
                tail_inits=None):
        gs = inc = None
        sc = 0.0
        if self._horizon_mode():
            # MPC mode (D10): score candidates against the K-slot predicted
            # channel and bill handovers off the deployed assignment.
            # ``rows`` maps a sliced sub-fleet back to its rows of the full
            # dynamics state so the rollout extrapolates the right users.
            gs = jnp.asarray(dynamics.predict_fleet_rollout(
                fleet, self.state, self.cfg.horizon, cfg=self.cfg.stream,
                rows=rows), jnp.float32)
            if init_assigns is not None:
                # Cold bootstraps have nothing deployed: no switching cost.
                inc = jnp.asarray(init_assigns, jnp.int32)
                sc = float(self.cfg.switch_cost)
        return fshard.solve_fleet_sharded(
            fleet, init_assigns, self.lam, self.sroa_cfg,
            self.cfg.max_rounds, self.cfg.escape_iters, mesh=self.mesh,
            top_k=self.cfg.top_k, n_starts=self.cfg.n_starts,
            gain_stacks=gs, switch_cost=sc, incumbents=inc,
            ladder=self.ladder, init_comps=init_comps,
            tail_inits=tail_inits)

    def _reprice(self) -> sroa.SroaResult:
        """Batched SROA of the current assignments under the live channel."""
        res = self.planner.allocate_fleet(
            self.fleet, jnp.asarray(self.assigns),
            jnp.asarray(self.comps) if self._comp_on else None)
        return jax.tree.map(np.asarray, res)

    def _bootstrap(self) -> None:
        out = self._engine(self.fleet, None)
        self.assigns = np.asarray(out.assign).copy()
        # Deployed compression levels ride with the assignments (level 0 ==
        # uncompressed when the ladder is off, so the array always exists).
        self.comps = np.asarray(out.comp).copy()
        # Receding-horizon warm-start stash (D10): each cell's previous
        # winning window pattern, fed to the next replan as an EXTRA engine
        # restart (so warm search never loses to cold).
        self._tail = (self.assigns.copy()
                      if self._horizon_mode() and self.cfg.warm_start
                      else None)
        self.alloc = self._reprice()
        self.gain_ref = np.asarray(self.fleet.cells.gain,
                                   np.float64).copy()
        self.R_ref = np.asarray(self.alloc.R, np.float64).copy()
        self._install_cache(np.arange(self.fleet.C))

    def prewarm(self) -> None:
        """Compile the engine for every replan-bucket size (and the mesh).

        Optional: without it the first tick that hits a new bucket size
        pays its compile inline, which pollutes latency percentiles.
        """
        C = self.fleet.C
        b = 1
        sizes = []
        while b < C:
            sizes.append(b)
            b <<= 1
        sizes.append(C)  # full-fleet replans trace differently from the
        #                  init=None bootstrap call — compile them too
        for b in sizes:
            idx = np.arange(b) % C
            sub = jax.tree.map(lambda x, i=idx: x[jnp.asarray(i)],
                               self.fleet)
            self._engine(sub, jnp.asarray(self.assigns[idx]), rows=idx,
                         init_comps=(jnp.asarray(self.comps[idx])
                                     if self._comp_on else None))

    # --------------------------------------------------------------- cache
    def _cell_row(self, i: int) -> Scenario:
        """Cell i as a full-width (padded) Scenario row."""
        return jax.tree.map(lambda x: x[i], self.fleet.cells)

    def _install_cache(self, idx: np.ndarray) -> None:
        """Publish fresh plans into the FleetPlanner's LRU cache."""
        for i in np.asarray(idx, int):
            mask = self.state.active[i]
            key = scenario_digest(self._cell_row(i), self.lam,
                                  None if mask.all() else mask,
                                  extra=self.planner._ladder_extra)
            plan = PlanResult(
                assign=self.assigns[i].copy(), b=self.alloc.b[i],
                f=self.alloc.f[i], p=self.alloc.p[i],
                R=float(self.alloc.R[i]), t=float(self.alloc.t[i]),
                cached=False, solve_calls=0, plan_ms=0.0,
                comp=(self.comps[i].copy() if self._comp_on else None))
            self.planner._insert(key, plan)

    # -------------------------------------------------------------- replan
    def _bucket(self, k: int) -> int:
        if not self.cfg.bucket:
            return k
        b = 1
        while b < k:
            b <<= 1
        return min(b, self.fleet.C)

    def _replan(self, idx: np.ndarray,
                ev: dynamics.FleetEvents | None) -> None:
        """One engine call re-searching the drifted cells (bucket-padded).

        Counts the batched loop's trip (its slowest row's rounds), the
        rounds of the real rows, the bucket's rows and cells, escapes, and
        the elements and tile lanes of the program's batched inversions;
        with the ladder on, also the real rows' accepted moves and those
        of them that changed a level.
        """
        tel = self.telemetry
        k = idx.size
        with tel.span("svc.research.gather"):
            pidx = np.concatenate(
                [idx, np.full(self._bucket(k) - k, idx[0], idx.dtype)])
            jidx = jnp.asarray(pidx)
            sub = jax.tree.map(lambda x: x[jidx], self.fleet)
            init = icomp = None
            if self.cfg.warm_start:
                init = self.assigns[pidx].copy()
                if ev is not None and ev.arrived[pidx].any():
                    # Churn arrivals have no searched assignment yet: seed
                    # them at their nearest edge (Alg 5 line 5) before the
                    # polish.
                    ne = np.asarray(fbatch.fleet_assignments(sub))
                    init = np.where(ev.arrived[pidx], ne, init)
                init = jnp.asarray(init, jnp.int32)
                if self._comp_on:
                    # Arrivals start uncompressed; survivors keep their
                    # level.
                    ic = self.comps[pidx].copy()
                    if ev is not None:
                        ic = np.where(ev.arrived[pidx], 0, ic)
                    icomp = jnp.asarray(ic, jnp.int32)
            # Receding-horizon warm start (D10): the previous window's
            # winner rides as one extra restart row (engine re-homes it off
            # closed edges), so warm MPC search never loses to a cold one.
            tails = (jnp.asarray(self._tail[pidx], jnp.int32)
                     if self._tail is not None else None)
        with tel.span("svc.research.engine"):
            out = self._engine(sub, init, rows=pidx, init_comps=icomp,
                               tail_inits=tails)
            if self._comp_on:
                # The levels and the trace's moves come back in the
                # assignment's one fetch.
                assign, comp, moves = jax.device_get(
                    (out.assign, out.comp, out.trace.moves))
            else:
                assign = np.asarray(out.assign)
        with tel.span("svc.research.scatter"):
            self.assigns[idx] = assign[:k]
            if self._comp_on:
                self.comps[idx] = comp[:k]
                # Accepted moves of the real rows (padding repeats idx[0]),
                # and those that changed a level.
                moved = moves[:k, :, 4] == 1
                tel.count("research.moves", moved.sum())
                tel.count("research.comp_moves",
                          (moved & (moves[:k, :, 3] == fengine.KIND_COMP))
                          .sum())
            if self._tail is not None:
                self._tail[idx] = assign[:k]
            rounds = np.asarray(out.rounds)
            tel.count("research.trip", rounds.max())
            tel.count("research.row_rounds", rounds[:k].sum())
            tel.count("research.rows", pidx.size)
            tel.count("research.cells", k)
            tel.count("research.escapes", np.asarray(out.escapes)[:k].sum())
            # The tile fill of the program's SROA inversions
            # (research.invert_fill = elements / lanes), from its shapes:
            # each device runs its share of the rows.
            ndev = 1 if self.mesh is None else self.mesh.devices.size
            for shape in fengine.inversion_batches(
                    -(-pidx.size // ndev), self.fleet.N_max, self.fleet.M,
                    self.cfg.top_k, self.cfg.n_starts, tails is not None,
                    self.cfg.horizon if self._horizon_mode() else 1,
                    self.ladder):
                elements, tiles, _ = sroa.invert_tiles(shape)
                tel.count("research.invert_elements", ndev * elements)
                tel.count("research.invert_lanes",
                          ndev * tiles * sroa.TILE)

    # ------------------------------------------------------------- topology
    def _redesign_topology(self) -> int:
        """Slow-timescale edge redesign (D12): rerun the bilevel search.

        Runs :func:`repro.fleet.topology.design_topology` from the CURRENT
        mask and assignments (warm bilevel restart), installs the winning
        mask on the live fleet and refreshes plans/caches for every cell
        whose topology changed.  Returns the number of accepted moves.
        """
        from repro.fleet import topology as ftopo
        tcfg = self.cfg.topology or ftopo.TopologyConfig()
        old = np.asarray(self.fleet.cells.edge_mask, bool).copy()
        res = ftopo.design_topology(
            self.fleet, self.lam, self.sroa_cfg, tcfg,
            init_assigns=self.assigns,
            max_rounds=self.cfg.max_rounds,
            escape_iters=self.cfg.escape_iters,
            top_k=self.cfg.top_k, n_starts=self.cfg.n_starts)
        moved = np.flatnonzero(
            (np.asarray(res.edge_mask, bool) != old).any(axis=1))
        if moved.size:
            self.fleet = res.fleet
            self.assigns[moved] = res.assigns[moved]
            if self._tail is not None:
                self._tail[moved] = res.assigns[moved]
            # New sites mean new geometry references: reset the drift
            # baseline so the redesign itself doesn't read as drift.
            self.alloc = self._reprice()
            self.gain_ref[moved] = np.asarray(self.fleet.cells.gain,
                                              np.float64)[moved]
            self.R_ref[moved] = np.asarray(self.alloc.R, np.float64)[moved]
            self._install_cache(moved)
        return len(res.history)

    # ---------------------------------------------------------------- serve
    def submit(self) -> PlanRequest:
        """Enqueue a plan request; the next tick resolves it."""
        self.telemetry.requests += 1
        return self.queue.submit(key=self.tick_idx)

    def tick(self, advance: bool = True) -> TickRecord:
        """One control-plane tick: dynamics, drift, replan, serve.

        Each stage runs in a telemetry span (``svc.*``); together the
        top-level spans cover the whole tick.
        """
        tel = self.telemetry
        with tel.tick():
            C = self.fleet.C
            with tel.span("svc.dynamics"):
                prev_assigns = self.assigns.copy()
                prev_active = np.asarray(self.state.active, bool).copy()
                ev = None
                if advance:
                    cm = self.rng.uniform(size=C) < self.cfg.event_rate
                    self.fleet, self.state, ev = dynamics.fleet_step(
                        self.fleet, self.state, self.rng,
                        cfg=self.cfg.stream, spec=self.spec, cell_mask=cm)
                gain_now = np.asarray(self.fleet.cells.gain, np.float64)

            # Slow-timescale topology redesign (D12): every P ticks,
            # re-open the edge placement question under the drifted
            # geometry (it moves sites, never the channel gains).
            topo_moves = 0
            if (self.cfg.topology_period and self.tick_idx > 0
                    and self.tick_idx % self.cfg.topology_period == 0
                    and self.fleet.cells.edge_mask is not None):
                with tel.span("svc.topology"):
                    topo_moves = self._redesign_topology()

            with tel.span("svc.reprice"):
                alloc = self._reprice()
            alloc_calls = 1
            with tel.span("svc.drift"):
                report = fdrift.score(gain_now, self.gain_ref,
                                      self.state.active, np.asarray(alloc.R),
                                      self.R_ref, self.cfg.drift)
                # Churn forces a re-search both ways: arrivals need a first
                # assignment, and departures free bandwidth/compute the
                # survivors' optimum shifts onto — drift scoring alone can
                # miss either (the repriced R of a shrunken cell DROPS,
                # which never trips the objective gate).
                forced = (ev.arrived.any(axis=1) | ev.departed.any(axis=1)
                          if ev is not None else np.zeros(C, bool))
                if self.cfg.replan_all:
                    idx = np.arange(C)
                else:
                    idx = np.flatnonzero(report.replan | forced)

            engine_calls = 0
            if idx.size:
                with tel.span("svc.research"):
                    self._replan(idx, ev)
                engine_calls = 1
                with tel.span("svc.reprice"):
                    alloc = self._reprice()
                alloc_calls += 1
            with tel.span("svc.install"):
                self.alloc = alloc
                R_now = np.asarray(alloc.R, np.float64)
                if idx.size:
                    self.gain_ref[idx] = gain_now[idx]
                    self.R_ref[idx] = R_now[idx]
                    self._install_cache(idx)
                    tel.count("install.cells", idx.size)
                if self._comp_on:
                    live = np.asarray(self.state.active, bool)
                    tel.count("install.compressed",
                              (self.comps[live] != 0).sum())
                sum_R = float(R_now.sum())

            with tel.span("svc.respond"):
                groups = self.queue.drain()
                tick_ms = tel.tick_elapsed_ms()
                replanned = set(int(i) for i in idx)
                base = {
                    "tick": self.tick_idx,
                    "objective": sum_R,
                    "R": R_now.tolist(),
                    "assign": self.assigns.tolist(),
                    "replanned": sorted(replanned),
                    "comp": self.comps.tolist() if self._comp_on else None,
                    "cached": [i not in replanned for i in range(C)],
                    "drift_channel": report.channel.tolist(),
                    "plan_ms": tick_ms,
                }
                if self._comp_on:
                    # The slots that hold a user, so that ``comp`` reads as
                    # the active users' levels, and the accepted moves of
                    # the tick's re-search (its counters).
                    got = tel.last_tick.counters
                    base["active"] = live.tolist()
                    base["moves"] = {
                        "all": got.get("research.moves", 0),
                        "comp": got.get("research.comp_moves", 0)}
                served = 0
                coalesced = 0
                for reqs in groups.values():
                    resp = dict(base, coalesced=len(reqs))
                    coalesced = max(coalesced, len(reqs))
                    for r in reqs:
                        tel.record_request(r.resolve(resp))
                        served += 1
                tel.count("serve.requests", served)

            with tel.span("svc.telemetry"):
                changed = int(ev.changed.sum()) if ev is not None else 0
                # A handover is an edge change for a user active in BOTH
                # plans: churn arrivals (first edge) and departures (stale
                # slot) are free.
                active = np.asarray(self.state.active, bool)
                handovers = int(((prev_assigns != self.assigns)
                                 & prev_active & active).sum())
                tiers = np.asarray(self.fleet.cells.tier)
                # Tier ids of every active user in a re-searched cell: the
                # replan burden heterogeneity telemetry (D11) — who pays
                # for churn/drift.
                tier_replans = (tiers[idx][active[idx]] if idx.size
                                else None)
                comp_levels = (self.comps[active] if self._comp_on else None)
                tel.record_tick(
                    n_cells=C, n_changed=changed, n_replanned=idx.size,
                    engine_calls=engine_calls, alloc_calls=alloc_calls,
                    sum_R=sum_R, tick_ms=tick_ms,
                    drift_scores=report.channel,
                    objective_scores=report.objective, coalesced=coalesced,
                    handovers=handovers, tier_replans=tier_replans,
                    comp_levels=comp_levels)
                rec = TickRecord(tick=self.tick_idx, changed=changed,
                                 replanned=np.asarray(idx),
                                 engine_calls=engine_calls, sum_R=sum_R,
                                 served=served, coalesced=coalesced,
                                 tick_ms=tick_ms, drift=report,
                                 handovers=handovers, topo_moves=topo_moves)
                self.tick_idx += 1
        return rec

    def run(self, ticks: int) -> list[TickRecord]:
        """Advance the control plane ``ticks`` times (no request load)."""
        return [self.tick() for _ in range(ticks)]
