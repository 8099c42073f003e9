"""The ``metro.staggered`` cell on the CPU at a tiny size: its own traffic
(``pedestrian-staggered``, a Bernoulli(0.5) share of the cells advanced a
tick) through ``run_cell.execute``.  A sound run is correct and compares
searches; a run with the timed path broken underneath, and the bfloat16
control in the program's place, come out not correct.

The fleet keeps the cell's 16 cells, so that a tick re-searches a bucket of
several of them, but each holds 8 users and 3 edges under small solver
caps."""
import copy
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from bench import check, control, harness  # noqa: E402
import bench.run_cell as run_cell  # noqa: E402

SEED = 2**31 + 4646          # larger than 32 signed bits hold
SECONDS = 2.0


def _staggered_cell():
    cell = harness.resolve("metro.staggered")
    assert cell.traffic["event_rate"] == 0.5
    cfg = copy.deepcopy(cell.config)
    cfg.update(users_min=6)
    cfg["scenario"].update(N=8, M=3)
    cfg["sroa"] = {"b_iters": 12, "f_iters": 8, "p_iters": 6, "t_iters": 8}
    cfg["service"]["max_rounds"] = 4
    cell.config = cfg
    return cell


def _caps(cell):
    s = cell.config["sroa"]
    return (s["b_iters"], s["f_iters"], s["p_iters"], s["t_iters"])


def _run(cell):
    """A run driven as ``run_cell.execute`` drives it, and its gathering."""
    import jax
    service = harness.build(cell, jax.devices()[:1])
    harness.warm(service, cell.traffic["warm_share"])
    run = harness.Run(cell=cell, seed=SEED, seconds=SECONDS)
    harness.drive(service, run, harness.Spans(), harness.CompileClock())
    return run, check.gather(run)


def test_staggered_sound_run_is_correct_and_compares_searches():
    import jax
    cell = _staggered_cell()
    run, g = _run(cell)
    # Some of the cells are re-searched each tick, never all of them.
    sizes = [len(t.replanned) for t in run.ticks]
    assert min(sizes) >= 2 and max(sizes) < 16, sizes
    assert g["search"] and not g["missing"]
    assert check.compare(g, _caps(cell))["correct"]
    out = run_cell.execute(cell, SEED, SECONDS, False, jax.devices())
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}


def test_staggered_bfloat16_control_is_not_correct():
    cell = _staggered_cell()
    _, g = _run(cell)
    caps = _caps(cell)
    svc = cell.config["service"]
    ctrl = check.compare(control.answered_by_control(
        g, caps, svc["max_rounds"], svc["escape_iters"]), caps)
    assert not ctrl["correct"], ctrl["numbers"]


def _stale_reprice(monkeypatch):
    """A step that returns its state unchanged: no plan is re-priced."""
    from repro.fleet.service.control import PlanningService
    orig = PlanningService._reprice

    def stale(self):
        if not hasattr(self, "_first_alloc"):
            self._first_alloc = orig(self)
        return self._first_alloc
    monkeypatch.setattr(PlanningService, "_reprice", stale)


def _half_replanned(monkeypatch):
    """Half of the batch left out: a tick re-searches only the first half
    of its drifted cells."""
    from repro.fleet.service.control import PlanningService
    orig = PlanningService._replan

    def half(self, idx, ev):
        return orig(self, idx[: max(1, idx.size // 2)], ev)
    monkeypatch.setattr(PlanningService, "_replan", half)


def _altered_plan(monkeypatch):
    """An answer altered where it is produced: each re-priced R, 1% up."""
    from repro.fleet.service.control import PlanningService
    orig = PlanningService._reprice

    def altered(self):
        res = orig(self)
        return res._replace(R=np.asarray(res.R) * 1.01)
    monkeypatch.setattr(PlanningService, "_reprice", altered)


def _altered_search(monkeypatch):
    """An answer altered where it is produced: every re-search returns its
    start, so no cell's descent is deployed."""
    from repro.fleet.service import shard

    orig = shard.solve_fleet_sharded

    def undone(fleet, init_assigns=None, *a, **k):
        out = orig(fleet, init_assigns, *a, **k)
        if init_assigns is None:
            return out
        return out._replace(assign=init_assigns)
    monkeypatch.setattr(shard, "solve_fleet_sharded", undone)


@pytest.mark.parametrize("fault", [_stale_reprice, _half_replanned,
                                   _altered_plan, _altered_search])
def test_staggered_broken_timed_path_is_not_correct(monkeypatch, fault):
    import jax
    fault(monkeypatch)
    out = run_cell.execute(_staggered_cell(), SEED, SECONDS, False, jax.devices())
    assert not out["correct"], out["compared"]
