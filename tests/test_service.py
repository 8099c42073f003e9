"""Continuous planning service tests: batched fleet dynamics, tick
advancement, drift-gated selective replanning, request coalescing,
sharding fallback, and the load generator / telemetry contract.

All service fixtures share one (C=4, N=8, M=2) shape and one SroaConfig
so the engine/allocator compile once per test session.
"""
import dataclasses
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sroa, wireless
from repro.fleet import batch as fbatch
from repro.fleet import dynamics
from repro.fleet import engine as fengine
from repro.fleet.service import (DriftConfig, PlanningService, ServiceConfig,
                                 drift, run_load, solve_fleet_sharded)
from repro.runtime.sharding import cell_mesh

CFG = sroa.SroaConfig(b_iters=16, f_iters=10, p_iters=8, t_iters=10)
SPEC = dataclasses.replace(wireless.ScenarioSpec(), N=8, M=2)
LAM = 1.0


def make_fleet(seed=0, C=4):
    return fbatch.draw_fleet(seed, C, SPEC, n_range=(8, 8))


def make_service(seed=0, **cfg_kw):
    kw = dict(max_rounds=4, escape_iters=1)
    kw.update(cfg_kw)
    return PlanningService(make_fleet(), lam=LAM, sroa_cfg=CFG,
                           cfg=ServiceConfig(**kw), spec=SPEC, seed=seed)


# ------------------------------------------------------- batched fleet step
def test_fleet_step_advances_all_cells():
    fleet = make_fleet()
    state = dynamics.init_fleet_state(fleet, seed=0)
    rng = np.random.default_rng(0)
    fleet2, state2, ev = dynamics.fleet_step(fleet, state, rng, spec=SPEC)
    assert fleet2.cells.user_pos.shape == fleet.cells.user_pos.shape
    assert fleet2.cells.gain.shape == fleet.cells.gain.shape
    pos = np.asarray(fleet2.cells.user_pos)
    assert np.all(pos >= 0.0) and np.all(pos <= SPEC.side_m)
    assert np.all(np.asarray(fleet2.cells.gain) > 0)
    assert not np.allclose(pos, np.asarray(fleet.cells.user_pos))
    assert state2.t == state.t + 1.0 and state2.step == 1
    assert ev.changed.all()


def test_fleet_step_unmasked_cells_are_bit_identical():
    """Cells outside cell_mask keep every leaf EXACTLY — the drift
    detector and plan cache depend on bit-identity, not closeness."""
    fleet = make_fleet()
    state = dynamics.init_fleet_state(fleet, seed=0)
    rng = np.random.default_rng(1)
    cm = np.array([True, False, True, False])
    fleet2, state2, ev = dynamics.fleet_step(fleet, state, rng, spec=SPEC,
                                             cell_mask=cm)
    np.testing.assert_array_equal(ev.changed, cm)
    for name in ("user_pos", "gain", "c", "D"):
        a = np.asarray(getattr(fleet.cells, name))
        b = np.asarray(getattr(fleet2.cells, name))
        np.testing.assert_array_equal(a[~cm], b[~cm], err_msg=name)
    for name in ("user_pos", "gain"):  # c/D only change on churn arrivals
        a = np.asarray(getattr(fleet.cells, name))
        b = np.asarray(getattr(fleet2.cells, name))
        assert not np.array_equal(a[cm], b[cm]), name


def test_fleet_step_trace_is_seed_deterministic():
    """Same seed => same trace, independent of what anyone replans."""
    outs = []
    for _ in range(2):
        fleet = make_fleet()
        state = dynamics.init_fleet_state(fleet, seed=3)
        rng = np.random.default_rng(7)
        for _ in range(3):
            fleet, state, _ = dynamics.fleet_step(fleet, state, rng,
                                                  spec=SPEC)
        outs.append(np.asarray(fleet.cells.gain))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_fleet_step_churn_respects_slot_pool():
    fleet = make_fleet()
    state = dynamics.init_fleet_state(fleet, seed=0)
    rng = np.random.default_rng(2)
    scfg = dynamics.StreamConfig(arrival_rate=4.0, departure_rate=0.5)
    fleet2, state2, ev = dynamics.fleet_step(fleet, state, rng, cfg=scfg,
                                             spec=SPEC)
    assert state2.active.shape == (fleet.C, fleet.N_max)
    # Arrived slots are active; departed-and-not-refilled slots are not.
    assert np.all(~ev.arrived | state2.active)
    assert np.all(~(ev.departed & ~ev.arrived) | ~state2.active)
    np.testing.assert_array_equal(np.asarray(fleet2.mask), state2.active)
    np.testing.assert_array_equal(np.asarray(fleet2.n_users),
                                  state2.active.sum(axis=1))


# ----------------------------------------------------------- tick advancement
def test_tick_advances_dynamics_and_clock():
    svc = make_service(event_rate=1.0)
    pos0 = np.asarray(svc.fleet.cells.user_pos).copy()
    t0 = svc.state.t
    rec = svc.tick()
    assert svc.tick_idx == 1 and rec.tick == 0
    assert svc.state.t == t0 + svc.cfg.stream.dt
    assert not np.allclose(np.asarray(svc.fleet.cells.user_pos), pos0)
    assert rec.changed == svc.fleet.C
    assert np.isfinite(rec.sum_R)


def test_tick_without_advance_is_stable():
    """No dynamics, no drift -> nothing replans, responses are cached."""
    svc = make_service()
    req = svc.submit()
    rec = svc.tick(advance=False)
    assert rec.engine_calls == 0 and rec.replanned.size == 0
    resp = req.result(timeout=5)
    assert resp["replanned"] == [] and all(resp["cached"])
    np.testing.assert_allclose(resp["R"], svc.R_ref, rtol=1e-5)


# --------------------------------------------------- drift-gated replanning
def test_drift_triggers_selective_replan():
    """A channel shock in ONE cell replans that cell only; the untouched
    cells keep their cached plans (and say so in the response)."""
    svc = make_service()
    g = np.asarray(svc.fleet.cells.gain).copy()
    g[2] *= 10.0  # big fade on every link of cell 2
    svc.fleet = svc.fleet._replace(
        cells=svc.fleet.cells._replace(gain=jnp.asarray(g)))
    req = svc.submit()
    rec = svc.tick(advance=False)
    resp = req.result(timeout=5)
    assert resp["replanned"] == [2]
    assert resp["cached"] == [True, True, False, True]
    assert rec.engine_calls == 1
    # Follow-up tick: the replanned cell's drift reference was refreshed,
    # so nothing is stale anymore.
    rec2 = svc.tick(advance=False)
    assert rec2.replanned.size == 0 and rec2.engine_calls == 0


def test_drift_score_flags_only_shifted_cells():
    gain_ref = np.ones((3, 4, 2))
    gain_now = gain_ref.copy()
    gain_now[1] *= 1.5
    active = np.ones((3, 4), bool)
    rep = drift.score(gain_now, gain_ref, active,
                      R_now=np.array([100.0, 100.0, 103.0]),
                      R_ref=np.array([100.0, 100.0, 100.0]),
                      cfg=DriftConfig(channel_threshold=0.1,
                                      objective_threshold=0.02))
    np.testing.assert_allclose(rep.channel, [0.0, 0.5, 0.0])
    np.testing.assert_allclose(rep.objective, [0.0, 0.0, 0.03])
    np.testing.assert_array_equal(rep.replan, [False, True, True])


def test_replan_all_baseline_replans_everything():
    svc = make_service(replan_all=True, event_rate=1.0)
    rec = svc.tick()
    assert rec.replanned.size == svc.fleet.C
    assert rec.engine_calls == 1   # still ONE batched call for all cells


# --------------------------------------------------------------- coalescing
def test_concurrent_requests_coalesce_into_one_engine_call():
    """K concurrent requests for one fleet/tick -> 1 engine call."""
    svc = make_service(replan_all=True, event_rate=1.0)
    K = 5
    reqs = [None] * K

    def client(i):
        reqs[i] = svc.submit()

    threads = [threading.Thread(target=client, args=(i,)) for i in range(K)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rec = svc.tick()
    assert rec.served == K and rec.engine_calls == 1
    assert rec.coalesced == K
    resps = [r.result(timeout=5) for r in reqs]
    assert all(r["coalesced"] == K for r in resps)
    assert all(r["tick"] == resps[0]["tick"] for r in resps)
    assert all(r["assign"] == resps[0]["assign"] for r in resps)


def test_requests_resolve_across_ticks_independently():
    svc = make_service()
    r1 = svc.submit()
    svc.tick(advance=False)
    r2 = svc.submit()
    svc.tick(advance=False)
    assert r1.result(timeout=5)["tick"] == 0
    assert r2.result(timeout=5)["tick"] == 1


# ----------------------------------------------------------------- sharding
def test_sharded_solve_single_device_fallback():
    """mesh=None (and a 1-device world) degrades to the plain engine."""
    fleet = make_fleet(seed=4, C=3)
    want = fengine.solve_fleet_assignments(fleet, lam=LAM, cfg=CFG,
                                           max_rounds=4, escape_iters=1)
    got = solve_fleet_sharded(fleet, lam=LAM, cfg=CFG, max_rounds=4,
                              escape_iters=1, mesh=None)
    np.testing.assert_array_equal(np.asarray(got.assign),
                                  np.asarray(want.assign))
    np.testing.assert_allclose(np.asarray(got.R), np.asarray(want.R),
                               rtol=1e-6)
    if jax.device_count() == 1:
        assert cell_mesh() is None  # service auto-falls back on CI


@pytest.mark.slow
def test_sharded_solve_multidevice_parity():
    """shard_map over 2 forced host devices == the single-device engine
    (including the pad-to-device-multiple path: C=3 on 2 devices)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import dataclasses
import numpy as np
from repro.core import sroa, wireless
from repro.fleet import batch as fbatch
from repro.fleet import engine as fengine
from repro.fleet.service import solve_fleet_sharded
from repro.runtime.sharding import cell_mesh

spec = dataclasses.replace(wireless.ScenarioSpec(), N=8, M=2)
fleet = fbatch.draw_fleet(4, 3, spec, n_range=(8, 8))
cfg = sroa.SroaConfig(b_iters=16, f_iters=10, p_iters=8, t_iters=10)
mesh = cell_mesh()
assert mesh is not None and mesh.devices.size == 2
got = solve_fleet_sharded(fleet, lam=1.0, cfg=cfg, max_rounds=4,
                          escape_iters=1, mesh=mesh)
want = fengine.solve_fleet_assignments(fleet, lam=1.0, cfg=cfg,
                                       max_rounds=4, escape_iters=1)
np.testing.assert_array_equal(np.asarray(got.assign),
                              np.asarray(want.assign))
np.testing.assert_allclose(np.asarray(got.R), np.asarray(want.R),
                           rtol=1e-5)
print("SHARD-PARITY-OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert "SHARD-PARITY-OK" in out.stdout, out.stderr[-2000:]


# ------------------------------------------------------ loadgen + telemetry
def test_run_load_poisson_telemetry_contract():
    svc = make_service(event_rate=0.5)
    snap = run_load(svc, ticks=4, req_per_tick=2.0, seed=1,
                    warmup_ticks=1)
    for key in ("plans_per_s", "requests_per_s", "replan_fraction",
                "latency_ms", "tick_ms", "drift_hist", "engine_calls",
                "objective_sum"):
        assert key in snap, key
    assert snap["ticks"] == 4
    assert snap["unserved"] == 0
    assert 0.0 <= snap["replan_fraction"] <= 1.0
    assert snap["plans_per_s"] > 0
    assert snap["latency_ms"]["p99"] >= snap["latency_ms"]["p50"] >= 0
    assert sum(snap["drift_hist"].values()) == 4 * svc.fleet.C
    # The telemetry record must be JSON-serializable (the emit contract).
    import json
    json.loads(svc.telemetry.emit())


def test_service_prewarm_compiles_buckets_without_mutating_plans():
    svc = make_service()
    assigns = svc.assigns.copy()
    svc.prewarm()
    np.testing.assert_array_equal(svc.assigns, assigns)


# ------------------------------------------------------- churn-forced replans
def test_departure_only_churn_forces_replan():
    """ISSUE 8 regression: a cell that only LOSES users must re-search.

    Departures free bandwidth/compute the survivors' optimum shifts onto,
    but the repriced R of a shrunken cell DROPS — the objective drift gate
    never fires — so the forced set must include departures, not just
    arrivals."""
    svc = make_service(
        event_rate=1.0,
        stream=dynamics.StreamConfig(arrival_rate=0.0, departure_rate=0.7),
        drift=DriftConfig(channel_threshold=10.0, objective_threshold=10.0))
    prev_active = svc.state.active.copy()
    rec = svc.tick()
    departed = (prev_active & ~svc.state.active).any(axis=1)
    arrived = (~prev_active & svc.state.active).any(axis=1)
    assert departed.any()          # seed chosen so cells actually shrink
    assert not arrived.any()       # arrival_rate=0: departure-only tick
    # Every departure-hit cell was re-searched despite zero drift signal.
    assert set(np.flatnonzero(departed)) <= set(rec.replanned.tolist())


# ----------------------------------------------------- telemetry edge cases
def test_drift_histogram_underflow_bin_conserves_counts():
    """Signed drift scores must all land in SOME bin: negative objective
    drift (a replanned cell beating its reference R) goes to `<0`."""
    from repro.fleet.service.telemetry import Telemetry

    t = Telemetry()
    scores = np.array([-0.5, -1e-9, 0.0, 0.003, 0.07, 2.0])
    t.record_tick(n_cells=6, n_changed=0, n_replanned=0, engine_calls=0,
                  alloc_calls=1, sum_R=0.0, tick_ms=1.0,
                  drift_scores=scores, objective_scores=scores)
    snap = t.snapshot()
    for hist in (snap["drift_hist"], snap["objective_drift_hist"]):
        assert hist["<0"] == 2
        assert sum(hist.values()) == scores.size  # conservation


def test_service_objective_hist_conserves_over_ticks():
    svc = make_service(event_rate=1.0)
    ticks = 3
    svc.run(ticks)
    snap = svc.telemetry.snapshot()
    assert sum(snap["objective_drift_hist"].values()) == ticks * svc.fleet.C
    assert sum(snap["drift_hist"].values()) == ticks * svc.fleet.C


def test_telemetry_snapshot_empty_window_roundtrips():
    import json

    from repro.fleet.service.telemetry import Telemetry

    t = Telemetry()
    snap = t.snapshot()
    assert snap["ticks"] == 0 and snap["requests_served"] == 0
    assert snap["plans_per_s"] == 0.0 and snap["replan_fraction"] == 0.0
    assert snap["latency_ms"] == {"p50": 0.0, "p99": 0.0, "max": 0.0}
    assert snap["handovers"] == 0
    assert sum(snap["drift_hist"].values()) == 0
    assert json.loads(json.dumps(snap)) == snap


def test_telemetry_requests_vs_served_stay_consistent():
    svc = make_service()
    req = svc.submit()
    assert svc.telemetry.requests == 1 and svc.telemetry.served == 0
    svc.tick(advance=False)
    req.result(timeout=5)
    assert svc.telemetry.served == svc.telemetry.requests == 1
    snap = svc.telemetry.snapshot()
    assert snap["requests_served"] == 1


def test_tick_reports_handovers_of_surviving_users_only():
    """Handovers count active-in-both-plans edge changes; a no-dynamics
    tick with no replan hands nobody over."""
    svc = make_service()
    rec = svc.tick(advance=False)
    assert rec.handovers == 0 and svc.telemetry.handovers == 0
    # Force a full re-search under a shocked channel: any edge change now
    # IS a handover, and telemetry accumulates the same count.
    g = np.asarray(svc.fleet.cells.gain).copy()
    g[:, :4, :] *= 25.0
    svc.fleet = svc.fleet._replace(
        cells=svc.fleet.cells._replace(gain=jnp.asarray(g)))
    prev = svc.assigns.copy()
    rec2 = svc.tick(advance=False)
    want = int(((prev != svc.assigns) & svc.state.active).sum())
    assert rec2.handovers == want
    assert svc.telemetry.handovers == want
