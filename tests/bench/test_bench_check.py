"""The comparison that decides ``correct``, on the CPU at a tiny size: a
sound run passes; a run with the timed path broken underneath, and the
bfloat16 control in the program's place, come out not correct.

Each run skips the harness's look for a chip and drives the rest of a run
(``run_cell.execute``) on a 2-cell fleet of 8 users and 3 edges."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from bench import check, control, harness  # noqa: E402
import bench.run_cell as run_cell  # noqa: E402

SEED = 2**31 + 4242          # larger than 32 signed bits hold
SECONDS = 1.5


def _tiny_cell():
    """``paper-metro`` under ``pedestrian-churn`` (``m8.churn``'s traffic),
    cut to the tiny size."""
    cell = harness.resolve("m8.churn")
    cfg = json.loads((ROOT / "bench/configs/paper-metro.json").read_text())
    cfg.update(cells=2, users_min=6)
    cfg["scenario"].update(N=8, M=3)
    cfg["sroa"] = {"b_iters": 12, "f_iters": 8, "p_iters": 6, "t_iters": 8}
    cfg["service"]["max_rounds"] = 4
    cell.config = cfg
    return cell


def _caps(cell):
    s = cell.config["sroa"]
    return (s["b_iters"], s["f_iters"], s["p_iters"], s["t_iters"])


def _execute(cell, trace=False):
    import jax
    return run_cell.execute(cell, SEED, SECONDS, trace, jax.devices())


def _gathered(cell):
    """A run's sampled inputs and outputs, as ``compare`` takes them."""
    import jax
    service = harness.build(cell, jax.devices()[:1])
    harness.warm(service, cell.traffic["warm_share"])
    run = harness.Run(cell=cell, seed=SEED, seconds=SECONDS)
    harness.drive(service, run, harness.Spans(), harness.CompileClock())
    return check.gather(run)


def test_sound_run_is_correct_and_reports_every_metric():
    cell = _tiny_cell()
    out = _execute(cell)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(out)[-1] == "compared"
    for v in out["compared"].values():
        assert v["value"] <= v["limit"]


def test_traced_run_reports_per_layer_metrics():
    cell = _tiny_cell()
    out = _execute(cell, trace=True)
    assert out["correct"], out["compared"]
    names = set(out["metrics"])
    assert {"tick.reprice_ms", "tick.research_ms", "tick.serve_ms",
            "window.compiles", "gen.late_p99_ms"} <= names
    assert out["metrics"]["window.compiles"]["value"] == 0
    assert "busy_s" in out["device"] and "breakdown" in out


def _stale_reprice(monkeypatch):
    """A step that returns its state unchanged: the plans are not re-priced
    under the new channel."""
    from repro.fleet.service.control import PlanningService
    orig = PlanningService._reprice

    def stale(self):
        if not hasattr(self, "_first_alloc"):
            self._first_alloc = orig(self)
        return self._first_alloc
    monkeypatch.setattr(PlanningService, "_reprice", stale)


def _half_replanned(monkeypatch):
    """Half of the batch left out: only the first half of the drifted
    cells is re-searched."""
    from repro.fleet.service.control import PlanningService
    orig = PlanningService._replan

    def half(self, idx, ev):
        return orig(self, idx[: max(1, idx.size // 2)], ev)
    monkeypatch.setattr(PlanningService, "_replan", half)


def _altered_plan(monkeypatch):
    """An answer altered where it is produced: one cell's re-priced R."""
    from repro.fleet.service.control import PlanningService
    orig = PlanningService._reprice

    def altered(self):
        res = orig(self)
        R = np.array(res.R, copy=True)
        R[0] *= 1.01
        return res._replace(R=R)
    monkeypatch.setattr(PlanningService, "_reprice", altered)


def _altered_search(monkeypatch):
    """An answer altered where it is produced: the searched assignment of
    every re-searched cell loses its descent (the start is deployed)."""
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
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = _execute(_tiny_cell())
    assert not out["correct"], out["compared"]


def test_bfloat16_control_is_not_correct():
    cell = _tiny_cell()
    g = _gathered(cell)
    caps = _caps(cell)
    assert check.compare(g, caps)["correct"]
    svc = cell.config["service"]
    ctrl = check.compare(control.answered_by_control(
        g, caps, svc["max_rounds"], svc["escape_iters"]), caps)
    assert not ctrl["correct"], ctrl["numbers"]
    assert ctrl["numbers"]["reprice_gap"]["value"] > \
        check.LIMITS["reprice_gap"]
