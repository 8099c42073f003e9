"""The committed ``tiered-ladder`` configuration (cell ``tiered.staggered``)
on the CPU, cut to the tiny size of ``test_bench_tiered``: it resolves
through BENCHMARK.json's entries, runs through ``run_cell.execute`` and
reads correct beside the tiny tiered cell and the plain ``metro.staggered``
world; its compression readers equal the program's counters, and read
nothing without a ladder; its served plans price as the reference prices
them at their deployed levels."""
import copy
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from bench import check, harness  # noqa: E402
import bench.run_cell as run_cell  # noqa: E402
from test_bench_tiered import SECONDS, SEED, _tiered_cell  # noqa: E402

COMMITTED = "bench/configs/tiered-ladder.json"
COMP_COUNTERS = ("research.moves", "research.comp_moves",
                 "install.compressed")
COMP_READERS = ("plan.compressed_share", "research.comp_move_share")


def _tiny(cfg: dict) -> dict:
    """``cfg`` cut to 2 cells of 8 users and 3 edges, small solver caps: the
    size of ``test_bench_tiered``'s cell."""
    cfg.update(cells=2, users_min=6)
    cfg["scenario"].update(N=8, M=3)
    cfg["sroa"] = {"b_iters": 12, "f_iters": 8, "p_iters": 6, "t_iters": 8}
    cfg["service"]["max_rounds"] = 4
    return cfg


def _cell_in(root: Path, workload: str, files: dict):
    """Cell ``workload`` resolved through BENCHMARK.json's entries in a copy
    of the benchmark under ``root``, with ``files`` (path -> JSON object)
    written over it; no other file of the benchmark is edited."""
    shutil.copytree(ROOT / "bench", root / "bench")
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file() and p.relative_to(root).as_posix() not in files}
    for rel, obj in files.items():
        (root / rel).write_text(json.dumps(obj))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    cell = harness.resolve(workload, root=root)
    for p, data in before.items():
        assert p.read_bytes() == data, p
    return cell


def _committed_cell(root: Path):
    """``tiered.staggered`` through the committed entries, with its
    configuration file cut to the tiny size: tiers and ladder as committed,
    traffic ``pedestrian-staggered`` as it is."""
    cfg = _tiny(json.loads((ROOT / COMMITTED).read_text()))
    return _cell_in(root, "tiered.staggered", {COMMITTED: cfg})


def _plain_cell(root: Path):
    """The same world and traffic without tiers or a ladder:
    ``metro.staggered`` cut to the tiny size."""
    cfg = _tiny(json.loads((ROOT / "bench/configs/paper-metro.json")
                           .read_text()))
    return _cell_in(root, "metro.staggered",
                    {"bench/configs/paper-metro.json": cfg})


CASES = {"synthetic": _tiered_cell, "committed": _committed_cell,
         "plain": _plain_cell}


def _execute(cell, mp):
    """``run_cell.execute`` on the CPU; also returns the ladder it compared
    with, the run as driven (its plans and searches kept) and each measured
    tick's program counters."""
    import jax
    seen = {"counters": []}
    compare = check.compare
    drive = harness.drive

    def kept(g, caps, ladder=None):
        seen.update(g=g, caps=caps, ladder=ladder)
        return compare(g, caps, ladder)

    def driven(service, run, *a, **k):
        tick = service.tick

        def counted(*ta, **tk):
            rec = tick(*ta, **tk)
            seen["counters"].append(
                dict(service.telemetry.last_tick.counters))
            return rec
        service.tick = counted
        seen["run"] = run
        return drive(service, run, *a, **k)
    mp.setattr(check, "compare", kept)
    mp.setattr(harness, "drive", driven)
    mp.setattr(harness.Run, "release", lambda self: None)
    out = run_cell.execute(cell, SEED, SECONDS, False, jax.devices())
    return out, seen


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """``case`` -> (cell, result, what was seen), each run once."""
    done = {}

    def run(case):
        if case not in done:
            with pytest.MonkeyPatch.context() as mp:
                cell = CASES[case](tmp_path_factory.mktemp(case))
                done[case] = (cell, *_execute(cell, mp))
        return done[case]
    return run


def test_committed_configuration_is_paper_metro_with_tifl_tiers_and_ladder():
    from repro.fed.compression import default_ladder
    cfg = json.loads((ROOT / COMMITTED).read_text())
    metro = json.loads((ROOT / "bench/configs/paper-metro.json").read_text())
    lad = default_ladder()
    assert [r["name"] for r in cfg["service"]["ladder"]] == [
        lv.name for lv in lad.levels]
    assert [(r["bytes_factor"], r["epoch_factor"])
            for r in cfg["service"]["ladder"]] == list(
        zip(lad.bytes_factors(), lad.epoch_factors()))
    tiers = cfg["scenario"]["tiers"]
    # TiFL's five groups of 4, 2, 1, 1/3 and 1/5 CPUs; the 4-CPU group at
    # the paper's cap.
    assert [t["f_scale"] for t in tiers] == [
        share / 4 for share in (4, 2, 1, 1 / 3, 1 / 5)]
    assert len({t["prob"] for t in tiers}) == 1
    assert all(t["cycle_mult"] == t["size_mult"] == 1 for t in tiers)
    # Everything else is paper-metro's.
    plain = copy.deepcopy(cfg)
    del plain["scenario"]["tiers"], plain["service"]["ladder"]
    for k in ("name", "source", "deployment", "assumed"):
        del plain[k], metro[k]
    assert plain == metro
    cell = harness.resolve("tiered.staggered")
    assert cell.chips == 1 and cell.config == cfg
    assert cell.traffic == json.loads(
        (ROOT / "bench/traffic/pedestrian-staggered.json").read_text())
    assert {m["name"] for m in cell.end_to_end} == {
        "plans_per_s", "cost_R_per_cell", "setup_s"}
    assert set(COMP_READERS) <= {m["name"] for m in cell.per_layer}
    assert not set(COMP_READERS) & {
        m["name"] for m in harness.resolve("metro.staggered").per_layer}


@pytest.mark.parametrize("case", ["synthetic", "committed", "plain"])
def test_cut_configuration_reads_correct(runs, case):
    cell, out, seen = runs(case)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert seen["ladder"] == harness.ladder_factors(cell.config)


def _moves(searches, k):
    """Accepted moves, and those that changed a level, of the first ``k``
    rows (the real cells) of each search."""
    n_all = n_comp = 0
    for s in searches:
        m = np.asarray(s["out"].trace.moves)[:k]
        moved = m[..., 4] == 1
        n_all += int(moved.sum())
        n_comp += int((moved & (m[..., 3] == check.KIND_COMP)).sum())
    return n_all, n_comp


@pytest.mark.parametrize("case", ["synthetic", "committed"])
def test_compression_readers_equal_the_program_counters(runs, case):
    """Each tick's counters equal what its captured searches and plan hold,
    and each reader equals its arithmetic on the counters of the ticks that
    answered a request."""
    _, _, seen = runs(case)
    run, counters = seen["run"], seen["counters"]
    assert len(counters) == len(run.ticks)
    served = {r.response["tick"] for r in run.requests if r.response}
    moves = comp = 0
    shares = []
    for t, got in zip(run.ticks, counters):
        n_all, n_comp = _moves(t.searches, len(t.replanned))
        assert got.get("research.moves", 0) == n_all
        assert got.get("research.comp_moves", 0) == n_comp
        active = t.plan["active"]
        assert got["install.compressed"] == (
            t.plan["comp"][active] != 0).sum()
        if t.plan["tick"] in served:
            moves += n_all
            comp += n_comp
            shares.append(100.0 * got["install.compressed"] / active.sum())
    # The ladder decides something in both.
    assert comp > 0 and np.mean(shares) > 0
    assert harness.reader("research.comp_move_share")(run) == pytest.approx(
        100.0 * comp / moves, rel=1e-12)
    assert harness.reader("plan.compressed_share")(run) == pytest.approx(
        np.mean(shares), rel=1e-12)


def test_compression_is_neither_counted_nor_read_without_a_ladder(runs):
    _, _, seen = runs("plain")
    assert seen["counters"]
    assert not any(set(COMP_COUNTERS) & set(c) for c in seen["counters"])
    for name in COMP_READERS:
        assert harness.reader(name)(seen["run"]) is None


def test_served_plans_price_as_the_reference_at_their_levels(runs):
    """Every plan the ladder-on service deployed, tick by tick and cell by
    cell, prices as the plain reference prices it at its levels, within the
    comparison's limit; some of them compress."""
    _, _, seen = runs("committed")
    run = seen["run"]
    items = []
    for t in run.ticks:
        p = t.plan
        for i in range(run.cells_C):
            items.append({
                "cell": check._cell(p["fleet"].cells, i),
                "mask": p["active"][i], "assign": p["assign"][i],
                "b": p["b"][i], "f": p["f"][i], "p": p["p"][i],
                "t": p["t"][i], "R": p["R"][i], "lam": p["lam"],
                "comp": p["comp"][i].astype(np.int32)})
    assert any(it["comp"][it["mask"]].any() for it in items)
    gap = check.reprice_gap(items, seen["caps"], seen["ladder"])
    assert gap <= check.LIMITS["reprice_gap"], gap
