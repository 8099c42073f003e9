"""The benchmark harness on the CPU: resolution of its files by name, the
open-loop generator, the window rule, the metric arithmetic, the trace
reduction and the refusal of a machine without a TPU."""
import json
import shutil
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from bench import devtrace, harness, stats  # noqa: E402
import bench.run_cell as run_cell  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


# ------------------------------------------------------------ resolution
def test_every_cell_resolves_with_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.resolve(w["name"])
        assert cell.config["scenario"]["M"] >= 2
        assert cell.traffic["request_rate_per_s"] > 0
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.reader(m["name"]))
    names = {m["name"] for m in spec["end_to_end"]}
    assert names == {"plans_per_s", "latency_p95_ms", "cost_R_per_cell",
                     "setup_s"}
    for m in spec["per_layer"]:
        assert m["moves"] in names
    for t in harness.span_targets().values():
        assert "service" in t or {"module", "attr"} <= set(t)


def test_unknown_names_are_refused():
    with pytest.raises(harness.CellError):
        harness.resolve("no.such.cell")
    with pytest.raises(harness.CellError):
        harness.reader("no.such.metric")


def test_new_cell_config_traffic_and_metric_are_files_plus_entries(tmp_path):
    """A later change adds a configuration, a traffic mix, a cell and a
    per-layer metric as new files and new entries; no existing file of the
    benchmark is edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/paper-metro.json").read_text())
    cfg["cells"] = 4
    (tmp_path / "bench/configs/dummy.json").write_text(json.dumps(cfg))
    tr = json.loads((ROOT / "bench/traffic/pedestrian-churn.json").read_text())
    tr["event_rate"] = 0.05
    (tmp_path / "bench/traffic/dummy-calm.json").write_text(json.dumps(tr))
    (tmp_path / "bench/metrics/dummy.ticks.py").write_text(
        "def read(run):\n    return len(run.ticks) or None\n")
    spec["configs"].append({"name": "dummy", "source": "x",
                            "file": "bench/configs/dummy.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "dummy.calm", "config": "dummy",
                              "traffic": "dummy-calm", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "dummy.ticks", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "tick", "moves": "plans_per_s",
                              "workloads": ["dummy.calm"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.resolve("dummy.calm", root=tmp_path)
    assert cell.config["cells"] == 4 and cell.traffic["event_rate"] == 0.05
    assert "dummy.ticks" in [m["name"] for m in cell.per_layer]
    read = harness.reader("dummy.ticks", root=tmp_path)
    assert read(SimpleNamespace(ticks=[1, 2, 3])) == 3
    other = harness.resolve("m8.churn", root=tmp_path)
    assert "dummy.ticks" not in [m["name"] for m in other.per_layer]
    for p, data in before.items():
        assert p.read_bytes() == data, p


# ------------------------------------------------------------ platform
def test_run_cell_refuses_cpu(capsys):
    import jax
    assert jax.devices()[0].platform == "cpu"
    rc = run_cell.main(["--workload", "m8.churn", "--seed", "1",
                        "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert not any(ln.startswith("{") for ln in out.out.splitlines())
    assert "TPU" in out.err


def test_run_cell_refuses_unknown_workload(capsys):
    assert run_cell.main(["--workload", "nope", "--seed", "1",
                          "--seconds", "1"]) != 0
    assert not capsys.readouterr().out.strip()


# ------------------------------------------------------------ open loop
def test_poisson_offsets_are_seeded_and_inside_the_window():
    a = harness.poisson_offsets(10.0, 30.0, 2**31 + 5)
    b = harness.poisson_offsets(10.0, 30.0, 2**31 + 5)
    c = harness.poisson_offsets(10.0, 30.0, 7)
    assert np.array_equal(a, b) and not np.array_equal(a[:5], c[:5])
    assert a.min() > 0 and a.max() < 30.0 and np.all(np.diff(a) > 0)
    assert 200 < a.size < 400          # 10 req/s over 30 s


def test_open_loop_sends_at_due_times_and_records_lateness():
    sent = []

    def submit():
        sent.append(time.perf_counter())
        return SimpleNamespace()

    t0 = time.perf_counter() + 0.05
    gen = harness.OpenLoop(submit, np.array([0.0, 0.02, 0.04]), t0)
    gen.start()
    gen.join(timeout=5)
    assert gen.finished.is_set() and len(sent) == 3
    for r in gen.requests:
        assert r.sent >= r.due - 1e-4     # never early
        assert r.sent - r.due < 0.05      # and not starved here
    late = [(r.sent - r.due) * 1e3 for r in gen.requests]
    reader = harness.reader("gen.late_p99_ms")
    assert reader(SimpleNamespace(requests=gen.requests)) == pytest.approx(
        float(np.percentile(late, 99)))


class _FakeService:
    """A service whose tick takes ``tick_s`` and serves the queue."""

    def __init__(self, tick_s):
        from repro.fleet.service.queue import CoalescingQueue
        self.tick_s, self.tick_idx = tick_s, 0
        self.queue = CoalescingQueue()
        self.fleet = SimpleNamespace(C=4)
        self.lock = threading.Lock()

    def submit(self):
        return self.queue.submit(key=self.tick_idx)

    def _engine(self, *a, **k):
        raise AssertionError("not called")

    def tick(self):
        time.sleep(self.tick_s)
        resp = {"tick": self.tick_idx}
        for reqs in self.queue.drain().values():
            for r in reqs:
                r.resolve(resp)
        self.tick_idx += 1
        return SimpleNamespace(replanned=np.arange(2), sum_R=8.0, served=0)


def test_window_ends_at_first_tick_after_the_last_answer(monkeypatch):
    """The generator stops issuing at --seconds; the window closes at the
    first tick end with nothing pending, at most two ticks later."""
    monkeypatch.setattr(harness, "plan_table", lambda s: {})
    svc = _FakeService(tick_s=0.1)
    cell = SimpleNamespace(traffic={"request_rate_per_s": 40.0,
                                    "cost_ticks": 2})
    run = harness.Run(cell=cell, seed=3, seconds=0.5)
    harness.drive(svc, run, harness.Spans(), SimpleNamespace(lowered=0))
    assert run.requests and all(r.done is not None for r in run.requests)
    assert run.window_s >= 0.5
    assert run.window_s <= 0.5 + 2 * 0.1 + 0.05
    last_due = max(r.due for r in run.requests)
    assert run.window[1] >= max(r.done for r in run.requests) >= last_due
    # the metric arithmetic on this run
    assert harness.reader("plans_per_s")(run) == pytest.approx(
        4 * len(run.ticks) / run.window_s)
    lat = [(r.done - r.due) * 1e3 for r in run.requests]
    assert harness.reader("latency_p95_ms")(run) == pytest.approx(
        float(np.percentile(lat, 95)))
    assert max(lat) <= 2 * 100 + 50       # at most two ticks of waiting
    assert harness.reader("cost_R_per_cell")(run) == pytest.approx(2.0)
    assert harness.reader("research.bucket_fill")(run) is None  # no search


def test_traced_run_traces_the_last_tick_after_the_last_request(monkeypatch):
    """The profiler starts with the first tick after the generator's last
    request, and its trace is written once the window has closed."""
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k:
                        calls.append(("start", time.perf_counter())))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda:
                        calls.append(("stop", time.perf_counter())))
    monkeypatch.setattr(harness, "plan_table", lambda s: {})
    cell = SimpleNamespace(traffic={"request_rate_per_s": 40.0,
                                    "cost_ticks": 2})
    run = harness.Run(cell=cell, seed=5, seconds=0.5)
    harness.drive(_FakeService(tick_s=0.1), run, harness.Spans(),
                  SimpleNamespace(lowered=0), trace_dir="unused")
    assert [c for c, _ in calls] == ["start", "stop"]
    start, stop = calls[0][1], calls[1][1]
    assert start >= max(r.sent for r in run.requests)
    assert run.ticks[-2].t1 <= start <= run.ticks[-1].t0
    assert stop >= run.window[1] == run.ticks[-1].t1
    assert run.window_s <= 0.5 + 2 * 0.1 + 0.05


def test_percentile():
    assert stats.percentile([], 95) is None
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(range(101), 95) == pytest.approx(95.0)
    assert stats.percentile([0.0, 10.0], 95) == pytest.approx(9.5)


def test_span_readers_report_absent_not_zero():
    run = harness.Run(cell=None, seed=0, seconds=1)
    run.ticks = [harness.Tick(t0=0.0, t1=1.0, replanned=np.arange(3),
                              sum_R=1.0, rows_searched=4,
                              plan=None, searches=None)]
    run.span_targets = {"tick.dynamics": False, "tick.reprice": True,
                        "tick.drift": True, "tick.research": True}
    run.spans = {"tick.reprice": [(0.1, 0.3)], "tick.drift": [(0.3, 0.4)],
                 "tick.research": [(0.4, 0.9)]}
    assert harness.reader("tick.dynamics_ms")(run) is None
    assert harness.reader("tick.serve_ms")(run) is None
    assert harness.reader("tick.reprice_ms")(run) == pytest.approx(200.0)
    assert harness.reader("research.bucket_fill")(run) == pytest.approx(75.0)
    run.span_targets["tick.dynamics"] = True
    run.spans["tick.dynamics"] = [(0.0, 0.05)]
    assert harness.reader("tick.serve_ms")(run) == pytest.approx(150.0)
    assert harness.reader("device.idle_share")(run) is None


# ------------------------------------------------------------ trace
def test_trace_reduction_of_recorded_events():
    host = [("tick", 0.0, 10.0), ("tick.reprice", 1.0, 3.0),
            ("tick.research", 4.0, 9.0), ("tick.drift", 3.0, 3.5)]
    device = {"/device:TPU:0": [("fusion.1", 1.0, 2.0), ("while.2", 4.0, 8.0),
                                ("fusion.1", 2.0, 2.5), ("copy", 11.0, 12.0)]}
    out = devtrace.reduce(device, host)
    assert out["window_s"] == 10.0
    assert out["busy_s"] == pytest.approx(1.5 + 4.0)
    assert out["device_ops"][0] == ["while.2", 4.0]
    nested = devtrace.self_times([("while", 0.0, 4.0), ("fusion", 1.0, 2.0),
                                  ("fusion", 2.0, 3.0), ("copy", 5.0, 6.0)],
                                 0.0, 5.5)
    assert sorted(nested) == [("copy", 0.5), ("fusion", 1.0),
                              ("fusion", 1.0), ("while", 2.0)]
    assert dict(out["device_ops"])["fusion.1"] == pytest.approx(1.5)
    # gaps [8, 10], [2.5, 4], [0, 1], each named by the span at its middle
    assert out["idle_gaps"] == [["tick.research", 2.0], ["tick.drift", 1.5],
                                ["host: outside any span", 1.0]]


def test_trace_reduction_of_a_recorded_chip_trace():
    """A one-tick trace recorded on a TPU v5e (a jitted reduction inside a
    ``tick`` span): the device plane is found, busy time lies inside the
    window, and the breakdown names the program's ops."""
    out = devtrace.reduce_dir(str(DATA), {"tick.research"})
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"] and all(s > 0 for _, s in out["device_ops"])
    assert len(out["idle_gaps"]) <= devtrace.TOP
