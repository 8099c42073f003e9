"""Metrics read from the program's own spans and counters, and device time
by the program's named scopes: the readers on synthetic records, the scope
attribution on a program compiled here and on a trace recorded on a chip."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from bench import devscopes, devtrace, harness  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
FIXTURE = Path(__file__).resolve().parent / "scoped" / "tick.xplane.pb"

PROGRAM_READERS = ("svc.install_ms", "svc.respond_ms", "research.rounds",
                   "research.round_fill", "research.round_ms",
                   "engine.score_share", "setup.bootstrap_s")


def _record(t0, spans, counters):
    from repro.fleet.service.telemetry import TickSpans
    return TickSpans(t0=t0, t1=t0 + 1.0, spans=spans, counters=counters)


def _run(records, setup_ms=None, trace=None):
    run = harness.Run(cell=None, seed=0, seconds=1)
    run.ticks = [harness.Tick(t0=r.t0, t1=r.t1, replanned=np.arange(1),
                              sum_R=1.0, rows_searched=1, plan=None,
                              searches=None) for r in records]
    for t, r in zip(run.ticks, records):
        t.telemetry = r
    if setup_ms is not None:
        run.setup_ms = setup_ms
    run.trace = trace
    return run


def _two_ticks():
    """Tick 0 re-searched 3 cells in a bucket of 4 (trip 4); tick 1 did not
    re-search."""
    research = [("svc.research.engine", 0.2, 0.6, "svc.research"),
                ("svc.research", 0.1, 0.7, None),
                ("svc.install", 0.7, 0.75, None),
                ("svc.respond", 0.75, 0.76, None)]
    quiet = [("svc.install", 1.1, 1.1, None), ("svc.respond", 1.1, 1.13, None)]
    c0 = {"research.trip": 4, "research.row_rounds": 9, "research.rows": 4,
          "research.cells": 3, "research.escapes": 1, "install.cells": 3,
          "serve.requests": 2}
    return [_record(0.0, research, c0),
            _record(1.0, quiet, {"serve.requests": 0})]


def test_program_readers_compute_from_the_tick_records():
    trace = {"busy_s": 2.0, "device_scopes": [
        ["engine.score/sroa.alg4/sroa.alg2", 1.0], ["engine.score", 0.2],
        ["engine.final/sroa.alg4", 0.3], [devscopes.UNSCOPED, 0.5]]}
    run = _run(_two_ticks(), setup_ms={"svc.bootstrap": 12345.0},
               trace=trace)
    read = {n: harness.reader(n)(run) for n in PROGRAM_READERS}
    assert read["svc.install_ms"] == pytest.approx((50.0 + 0.0) / 2)
    assert read["svc.respond_ms"] == pytest.approx((10.0 + 30.0) / 2)
    assert read["research.rounds"] == 4          # the one re-search
    assert read["research.round_fill"] == pytest.approx(100 * 9 / (3 * 4))
    assert read["research.round_ms"] == pytest.approx(400.0 / 4)
    assert read["engine.score_share"] == pytest.approx(60.0)
    assert read["setup.bootstrap_s"] == pytest.approx(12.345)


def _without_records(run):
    for t in run.ticks:
        del t.telemetry


def _without_research(run):
    for t in run.ticks:
        t.telemetry.spans = [s for s in t.telemetry.spans
                             if not s[0].startswith("svc.research")]
        t.telemetry.counters = {}


@pytest.mark.parametrize("name", PROGRAM_READERS)
@pytest.mark.parametrize("strip", [_without_records, _without_research],
                         ids=["no-record", "no-research"])
def test_program_readers_report_absent_not_zero(name, strip):
    """A run whose ticks carry no record, or whose record lacks the span or
    counter, reads None: the metric is absent, never 0."""
    run = _run(_two_ticks())
    strip(run)
    value = harness.reader(name)(run)
    if strip is _without_research and name in ("svc.install_ms",
                                               "svc.respond_ms"):
        assert value is not None and value > 0
    else:
        assert value is None


# ------------------------------------------------------------ scopes
def test_scope_path_keeps_the_programs_scopes_outermost_first():
    op = ("jit(solve_fleet_assignments)/vmap()/while/body/engine.score/"
          "vmap(sroa.alg4)/while/body/sroa.alg3/while/body/sroa.alg2/"
          "while/body/closed_call/jit(_where)/select_n")
    assert devscopes.scope_path(op) == \
        "engine.score/sroa.alg4/sroa.alg3/sroa.alg2"
    assert devscopes.scope_path("jit(f)/while/body/add") == devscopes.UNSCOPED
    assert devscopes.scope_path("") == devscopes.UNSCOPED


def _hlo_proto(fn, *args):
    """A serialized ``HloProto`` of ``fn`` as compiled here (the profiler
    keeps the same message per program)."""
    import jax
    module = (jax.jit(fn).lower(*args).compile().runtime_executable()
              .hlo_modules()[0].as_serialized_hlo_module_proto())
    size, n = bytearray(), len(module)
    while True:
        size.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            break
    return bytes([0x0A]) + bytes(size) + module    # field 1: hlo_module


def test_hlo_op_names_give_a_fusion_its_roots_scopes():
    import jax
    import jax.numpy as jnp

    def fn(x):
        with jax.named_scope("engine.score"):
            y = jnp.sin(x) * 2.0 + 1.0
        with jax.named_scope("engine.final"):
            return jnp.tanh(y).sum()

    names = devscopes.hlo_op_names(_hlo_proto(fn, jnp.ones((8, 8))))
    paths = {n: devscopes.scope_path(op) for n, op in names.items()}
    fusions = {n: p for n, p in paths.items() if "fusion" in n}
    assert fusions, names
    assert set(fusions.values()) <= {"engine.score", "engine.final"}
    assert "engine.final" in paths.values()


def test_device_scopes_attribute_self_time_to_scope_paths():
    host = [("tick", 0.0, 10.0), ("svc.research", 1.0, 9.0)]
    device = {"/device:TPU:0": [
        ("%while.1 = ...", 1.0, 7.0),            # holds the next two ops
        ("%fusion.2 = ...", 1.0, 4.0),
        ("%fusion.3 = ...", 4.0, 5.0),
        ("%copy.4 = ...", 8.0, 8.5),
        ("%fusion.5 = ...", 12.0, 13.0)]}         # outside the window
    names = {"%while.1 = ...": "jit(f)/engine.score/while",
             "%fusion.2 = ...": "jit(f)/engine.score/while/body/sroa.alg2/x",
             "%fusion.3 = ...": "jit(f)/engine.final/y"}
    out = dict(devscopes.device_scopes(device, host, names))
    assert out == pytest.approx({"engine.score/sroa.alg2": 3.0,
                                 "engine.score": 2.0, "engine.final": 1.0,
                                 devscopes.UNSCOPED: 0.5})
    busy = devtrace.reduce(device, host)["busy_s"]
    assert sum(out.values()) == pytest.approx(busy)
    assert devscopes.device_scopes(device, host, {})[0] == \
        [devscopes.UNSCOPED, pytest.approx(busy)]


def test_device_scopes_of_a_recorded_chip_trace():
    """A tick recorded on a TPU v5e: a jitted loop under ``engine.score``
    inside ``svc.research``, its last step fused into an unscoped root, then
    ``svc.respond`` host work alone.  Scoped and unscoped device time add up
    to the busy time, the loop dominates, and the device gap is named by
    the program's span."""
    out = devscopes.reduce_file(str(FIXTURE))
    scoped = dict(out["device_scopes"])
    assert set(scoped) == {"engine.score", devscopes.UNSCOPED}
    assert sum(scoped.values()) == pytest.approx(out["busy_s"], rel=1e-6)
    assert scoped["engine.score"] > 0.9 * out["busy_s"]
    red = devtrace.reduce(*devtrace.read_xplane(
        str(FIXTURE), {"tick", "svc.research", "svc.respond"}))
    assert red["idle_gaps"][0][0] == "svc.respond"


def test_reduce_file_reads_the_older_fixture_as_unscoped():
    out = devscopes.reduce_file(str(DATA / "tick.xplane.pb"))
    assert out["device_scopes"] == [[devscopes.UNSCOPED,
                                     pytest.approx(out["busy_s"])]]
