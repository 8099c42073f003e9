"""A configuration that declares device tiers and a compression ladder, on
the CPU at a tiny size: it is added as files plus entries, runs through
``run_cell.execute`` and is checked at its deployed compression levels.  A
sound run is correct, replays compression moves and deploys a compressed
level; the program priced with a wrong rung, an altered level and the
bfloat16 control come out not correct; a malformed entry is refused."""
import copy
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from bench import check, control, harness  # noqa: E402
from bench import reference as ref  # noqa: E402
import bench.run_cell as run_cell  # noqa: E402

SEED = 2**31 + 4343          # larger than 32 signed bits hold
SECONDS = 1.5
TIERS = [{"name": "slow", "cycle_mult": 1.6, "f_scale": 0.55, "prob": 0.35},
         {"name": "mid"},
         {"name": "fast", "cycle_mult": 0.7, "size_mult": 1.2,
          "f_scale": 1.5, "prob": 0.3}]
LADDER = [{"name": "none", "bytes_factor": 1.0, "epoch_factor": 1.0},
          {"name": "int8", "bytes_factor": 0.25, "epoch_factor": 1.05},
          {"name": "topk0.05+int8", "bytes_factor": 0.0625,
           "epoch_factor": 1.3}]


def _tiered_config() -> dict:
    """The tiny cell of ``test_bench_check`` with tiers and three rungs."""
    cfg = json.loads((ROOT / "bench/configs/paper-metro.json").read_text())
    cfg.update(name="tiny-tiered", cells=2, users_min=6)
    cfg["scenario"].update(N=8, M=3, tiers=copy.deepcopy(TIERS))
    cfg["sroa"] = {"b_iters": 12, "f_iters": 8, "p_iters": 6, "t_iters": 8}
    cfg["service"].update(max_rounds=4, ladder=copy.deepcopy(LADDER))
    return cfg


def _tiered_cell(root: Path):
    """The tiered cell, added under ``root`` as a new configuration file, a
    new traffic file and new entries; no file of the benchmark is edited.

    Its traffic is pedestrian churn with ten times the departures of
    ``pedestrian-churn``, so that arrivals, which start uncompressed, keep
    coming into cells of 8 slots and every window's re-searches change
    levels."""
    shutil.copytree(ROOT / "bench", root / "bench")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench/configs/tiny-tiered.json").write_text(
        json.dumps(_tiered_config()))
    tr = json.loads((ROOT / "bench/traffic/pedestrian-churn.json")
                    .read_text())
    tr["stream"]["departure_rate"] *= 10
    (root / "bench/traffic/tiny-churn.json").write_text(json.dumps(tr))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-tiered", "source": "x",
                            "file": "bench/configs/tiny-tiered.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "tiered.churn", "config": "tiny-tiered",
                              "traffic": "tiny-churn", "chips": 1,
                              "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.resolve("tiered.churn", root=root)
    for p, data in before.items():
        assert p.read_bytes() == data, p
    return cell


def _execute(cell, mp):
    """``run_cell.execute`` on the CPU; also returns what it compared."""
    import jax
    seen = {}
    compare = check.compare

    def kept(g, caps, ladder=None):
        seen.update(g=g, caps=caps, ladder=ladder)
        return compare(g, caps, ladder)
    mp.setattr(check, "compare", kept)
    out = run_cell.execute(cell, SEED, SECONDS, False, jax.devices())
    return out, seen


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        cell = _tiered_cell(tmp_path_factory.mktemp("tiered"))
        out, seen = _execute(cell, mp)
    return cell, out, seen


def test_tiered_cell_is_correct_at_its_deployed_levels(sound):
    cell, out, seen = sound
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert seen["ladder"] == ((1.0, 1.0), (0.25, 1.05), (0.0625, 1.3))
    g = seen["g"]
    assert any(it["comp"].any() for it in g["reprice"])
    kinds = {int(m[3]) for it in g["search"]
             for m, v in zip(it["moves"], it["valid"]) if v and m[4]}
    assert check.KIND_COMP in kinds
    assert all("init_comp" in it and "served_comp" in it
               for it in g["search"])


def test_tiered_compression_move_off_the_current_levels_reads_inf(sound):
    _, _, seen = sound
    items = copy.deepcopy(seen["g"]["search"])
    it = next(it for it in items
              if any(v and m[3] == check.KIND_COMP
                     for m, v in zip(it["moves"], it["valid"])))
    r = next(r for r, m in enumerate(it["moves"])
             if it["valid"][r] and m[3] == check.KIND_COMP)
    it["moves"][r, 1] = (it["moves"][r, 1] + 1) % len(LADDER)
    assert check.search_gap(items, seen["caps"], seen["ladder"]) == np.inf


def test_tiered_bfloat16_control_is_not_correct(sound):
    cell, _, seen = sound
    svc = cell.config["service"]
    ctrl = check.compare(control.answered_by_control(
        seen["g"], seen["caps"], svc["max_rounds"], svc["escape_iters"],
        seen["ladder"]), seen["caps"], seen["ladder"])
    assert not ctrl["correct"], ctrl["numbers"]


def _rung_priced_wrong(mp):
    """The program prices the top rung's upload at twice its bytes; the
    configuration, and so the reference, keeps the stated factor."""
    from repro.fed.compression import CompressionLadder
    orig = CompressionLadder.bytes_factors

    def wrong(self):
        f = list(orig(self))
        f[-1] *= 2.0
        return tuple(f)
    mp.setattr(CompressionLadder, "bytes_factors", wrong)


def _levels_dropped(mp):
    """An answer altered where it is produced: every re-search deploys its
    assignment with every user back at level 0."""
    from repro.fleet.service import shard
    orig = shard.solve_fleet_sharded

    def dropped(*a, **k):
        out = orig(*a, **k)
        return out._replace(comp=np.zeros_like(np.asarray(out.comp)))
    mp.setattr(shard, "solve_fleet_sharded", dropped)


@pytest.mark.parametrize("fault", [_rung_priced_wrong, _levels_dropped])
def test_tiered_broken_timed_path_is_not_correct(tmp_path, fault):
    import jax
    # The fault changes what is traced, not the jit's keys: compile afresh
    # on both sides, so that no other test sees the broken programs.
    jax.clear_caches()
    try:
        with pytest.MonkeyPatch.context() as mp:
            fault(mp)
            out, _ = _execute(_tiered_cell(tmp_path), mp)
    finally:
        jax.clear_caches()
    assert not out["correct"], out["compared"]


def _reference_cell(seed=7, N=8, M=3):
    from repro.core.wireless import ScenarioSpec, draw_scenario
    scn = draw_scenario(seed, ScenarioSpec(N=N, M=M))
    return ({k: np.asarray(getattr(scn, k)) for k in ref.CELL_KEYS},
            np.asarray(scn.gain).argmax(axis=1).astype(np.int32),
            np.arange(N) < N - 1)


@pytest.mark.parametrize("fn", ["sroa", "evaluate", "score_neighbourhood"])
def test_reference_single_rung_ladder_is_bitwise_no_ladder(fn):
    import jax
    import jax.numpy as jnp
    caps = (12, 8, 6, 8)
    cell, assign, mask = _reference_cell()
    zero = np.zeros_like(assign)
    lam = jnp.float32(1.0)
    cell = ref.cast(cell, jnp.float32)

    def run(*lv):
        if fn == "sroa":
            return ref.sroa(cell, assign, mask, lam, caps, *lv)
        if fn == "evaluate":
            b, f, p = ref.sroa(cell, assign, mask, lam, caps)[:3]
            return ref.evaluate(cell, assign, b, f, p, lam, mask, *lv)
        return ref.score_neighbourhood(cell, assign, mask, lam, caps, *lv)

    plain = jax.jit(lambda: run())()
    one_rung = jax.jit(lambda: run(zero, ((1.0, 1.0),)))()
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(one_rung)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_reference_joint_neighbourhood_follows_the_engine_order():
    """Rows 1 + N(M-1) + u(L-1) + (k-1) raise user u's level by k, mod L,
    on the same assignment, as the engine's joint candidates do."""
    import jax.numpy as jnp
    from repro.fleet import engine
    assign = jnp.asarray([0, 2, 1, 1], jnp.int32)
    comp = jnp.asarray([0, 1, 2, 0], jnp.int32)
    mask = jnp.asarray([True, True, False, True])
    cands, comps, valid = ref.joint_neighbourhood(assign, comp, mask, 3, 3)
    e_cands, e_comps, e_valid = engine._comp_candidates(assign, comp, 3, 3,
                                                        mask)
    np.testing.assert_array_equal(cands, e_cands)
    np.testing.assert_array_equal(comps, e_comps)
    np.testing.assert_array_equal(valid, e_valid)
    assert cands.shape == (1 + 4 * 2 + 4 * 2, 4)


@pytest.mark.parametrize("where, entry", [
    ("tiers", "slow"),
    ("tiers", {"name": "slow", "cycle_mult": "1.6"}),
    ("tiers", {"name": "slow", "speed": 2.0}),
    ("tiers", {"cycle_mult": 1.6}),
    ("tiers", {"name": "slow", "cycle_mult": 0.0}),
    ("ladder", {"name": "half", "bytes_factor": 1.5}),
    ("ladder", {"name": "half", "bytes_factor": 0.5, "epoch": 1.1}),
    ("ladder", {"name": "half", "bytes_factor": True}),
    ("ladder", {"name": "half", "bytes_factor": 0.5}),
])
def test_malformed_tier_or_rung_is_refused(where, entry):
    import jax
    cell = harness.resolve("m8.churn")
    cfg = _tiered_config()
    section = "scenario" if where == "tiers" else "service"
    cfg[section][where] = cfg[section][where][:1] + [entry]
    cell.config = cfg
    with pytest.raises(harness.CellError, match=rf"{where}|cycle_mult"):
        harness.build(cell, jax.devices()[:1])


@pytest.mark.parametrize("where, value", [("tiers", []), ("ladder", {}),
                                          ("ladder", [LADDER[1]])])
def test_tiers_or_ladder_that_is_no_list_of_rungs_is_refused(where, value):
    import jax
    cell = harness.resolve("m8.churn")
    cfg = _tiered_config()
    cfg["scenario" if where == "tiers" else "service"][where] = value
    cell.config = cfg
    with pytest.raises(harness.CellError, match=where):
        harness.build(cell, jax.devices()[:1])
