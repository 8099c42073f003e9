"""One cell of the benchmark: resolve its files, build the planning service,
drive the measured window, and keep what the check and the metrics read.

Everything a cell is made of is found by name:

* ``BENCHMARK.json``             the cells, configurations and metrics;
* ``bench/configs/<name>.json``  a configuration (the deployment's sizes);
* ``bench/traffic/<name>.json``  a traffic mix, read by :func:`drive`;
* ``bench/spans/<name>.json``    a host span around one call into the program;
* ``bench/metrics/<name>.py``    the reader of one metric.

From the program the harness takes the system under test
(``PlanningService`` and the calls it makes into its layers) and nothing
else: traffic, spans, the metric arithmetic, the reference and the
comparison live here.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


class CellError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be resolved."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise CellError(f"missing file {path}")
    return json.loads(path.read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` with its configuration, traffic and metrics."""
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {workload!r} names no known config")
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(name=workload, chips=int(w["chips"]),
                config=config, traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, workload)])


def reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of metric ``name``, from its own file."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise CellError(f"no reader for metric {name!r} at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def span_targets(root: Path = ROOT) -> dict:
    """Span name -> ``{"module": ..., "attr": ...}`` or ``{"service": ...}``."""
    return {p.stem: json.loads(p.read_text())
            for p in sorted((root / "bench" / "spans").glob("*.json"))}


# ---------------------------------------------------------------- recording
class CompileClock:
    """The number of programs lowered, from JAX's own monitoring events:
    every program compiled, whether or not the persistent cache holds it."""

    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.lowered = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_span)

    def _on_span(self, event, duration, **_):
        if event == self._LOWER:
            self.lowered += 1


class Spans:
    """Host-clock spans around calls into the program's layers."""

    def __init__(self):
        self.spans: dict[str, list[tuple[float, float]]] = {}
        self.targets: dict[str, bool] = {}

    def _wrap(self, name, fn):
        from jax.profiler import TraceAnnotation
        out = self.spans.setdefault(name, [])

        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                # Named in the profiler's trace too, so that an idle gap of
                # the device can be put down to the host span it falls in.
                with TraceAnnotation(name):
                    return fn(*args, **kwargs)
            finally:
                out.append((t0, time.perf_counter()))
        return timed

    def install(self, service, targets: dict) -> None:
        """Wrap each target that exists; a missing one leaves its span absent."""
        for name, t in targets.items():
            if "service" in t:
                fn = getattr(service, t["service"], None)
                if fn is not None:
                    setattr(service, t["service"], self._wrap(name, fn))
            else:
                try:
                    mod = importlib.import_module(t["module"])
                except ImportError:
                    mod = None
                fn = getattr(mod, t["attr"], None)
                if fn is not None:
                    setattr(mod, t["attr"], self._wrap(name, fn))
            self.targets[name] = fn is not None


@dataclasses.dataclass
class Request:
    due: float
    sent: float | None = None
    handle: object = None

    @property
    def done(self) -> float | None:
        return getattr(self.handle, "bench_done", None)

    @property
    def response(self) -> dict | None:
        return getattr(self.handle, "bench_response", None)


class OpenLoop(threading.Thread):
    """Open-loop Poisson plan requests: each is sent at its due time,
    whether or not earlier ones were answered."""

    def __init__(self, submit, offsets: np.ndarray, t0: float):
        super().__init__(daemon=True, name="bench-open-loop")
        self.submit = submit
        self.requests = [Request(due=t0 + float(o)) for o in offsets]
        self.finished = threading.Event()

    def run(self):
        try:
            for r in self.requests:
                wait = r.due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                r.sent = time.perf_counter()
                r.handle = self.submit()
        finally:
            self.finished.set()


def _stamp_on_resolve(queue) -> None:
    """Time each request's response where the tick resolves it."""
    drain = queue.drain

    def timed_drain():
        groups = drain()
        for reqs in groups.values():
            for h in reqs:
                resolve = h.resolve

                def stamped(resp, h=h, resolve=resolve):
                    out = resolve(resp)
                    h.bench_done = time.perf_counter()
                    h.bench_response = resp
                    return out
                h.resolve = stamped
        return groups
    queue.drain = timed_drain


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    replanned: np.ndarray
    sum_R: float
    rows_searched: int  # rows of the replan buckets searched in the tick
    plan: dict          # the plan table after the tick (host arrays)
    searches: list      # engine calls made during the tick


@dataclasses.dataclass
class Run:
    """Everything one run recorded; metric readers take their numbers here."""

    cell: Cell
    seed: int
    seconds: float
    setup_s: float = 0.0
    window: tuple = (0.0, 0.0)
    ticks: list = dataclasses.field(default_factory=list)
    requests: list = dataclasses.field(default_factory=list)
    spans: dict = dataclasses.field(default_factory=dict)
    span_targets: dict = dataclasses.field(default_factory=dict)
    compiles_in_window: int | None = None
    trace: dict | None = None
    cells_C: int = 0

    def release(self) -> None:
        """Drop the references to the program's device arrays."""
        for t in self.ticks:
            t.plan = t.searches = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def tick_spans(self, name: str) -> list[float] | None:
        """Seconds of span ``name`` per measured tick; None if its target is
        gone."""
        if not self.span_targets.get(name):
            return None
        return [sum(b - a for a, b in self.spans.get(name, ())
                    if t.t0 <= a and b <= t.t1) for t in self.ticks]


# ---------------------------------------------------------------- the cell
def _made(where: str, make):
    """``make()``, with the program's refusal of a value as a CellError that
    names the configuration's key."""
    try:
        return make()
    except (TypeError, ValueError) as e:
        raise CellError(f"{where}: {e}") from e


def _objects(cfg: dict, section: str, key: str, needs: tuple = ()) -> list:
    """``cfg[section][key]``: a non-empty list of objects, each stating every
    field in ``needs``, whose fields other than ``name`` are numbers."""
    where = f"{section}.{key}"
    raw = cfg[section][key]
    if not isinstance(raw, list) or not raw:
        raise CellError(f"{where}: a non-empty list of objects, got {raw!r}")
    for i, e in enumerate(raw):
        if not isinstance(e, dict):
            raise CellError(f"{where}[{i}]: an object, got {e!r}")
        for k in needs:
            if k not in e:
                raise CellError(f"{where}[{i}]: states no {k}")
        for k, v in e.items():
            if k != "name" and (isinstance(v, bool)
                                or not isinstance(v, (int, float))):
                raise CellError(f"{where}[{i}].{k}: a number, got {v!r}")
    return raw


# A rung states every factor, so that the program and the reference price it
# from the file alike and neither falls back on a default of its own.
RUNG_FIELDS = ("name", "bytes_factor", "epoch_factor")


def _entries(cfg: dict, section: str, key: str, cls, needs=()) -> tuple:
    """``cfg[section][key]`` as a tuple of the program's ``cls``."""
    return tuple(_made(f"{section}.{key}[{i}]", lambda e=e: cls(**e))
                 for i, e in enumerate(_objects(cfg, section, key, needs)))


def ladder_factors(cfg: dict) -> tuple | None:
    """The configuration's compression ladder as the reference takes it: one
    (bytes_factor, epoch_factor) pair per rung; None without a ladder."""
    if "ladder" not in cfg["service"]:
        return None
    return tuple((float(e["bytes_factor"]), float(e["epoch_factor"]))
                 for e in _objects(cfg, "service", "ladder", RUNG_FIELDS))


def build(cell: Cell, devices):
    """The cell's ``PlanningService``, bootstrapped.

    The world (the fleet's draw and its dynamics) comes from the
    configuration's ``world_seed``, so every run does the same work; a run's
    ``--seed`` draws its request stream and the comparison's sample.

    ``scenario.tiers`` lists device tiers (``DeviceTier``'s fields) and
    ``service.ladder`` compression rungs (``CompressionLevel``'s fields, all
    stated, rung 0 the identity); a malformed entry raises CellError."""
    from repro.core import sroa
    from repro.core.wireless import DeviceTier, ScenarioSpec
    from repro.fed.compression import CompressionLadder, CompressionLevel
    from repro.fleet import draw_fleet, dynamics
    from repro.fleet.service import DriftConfig, PlanningService, ServiceConfig

    cfg, tr = cell.config, cell.traffic
    scn = {k: tuple(v) if isinstance(v, list) else v
           for k, v in cfg["scenario"].items()}
    if "tiers" in scn:
        scn["tiers"] = _entries(cfg, "scenario", "tiers", DeviceTier)
    spec = _made("scenario", lambda: ScenarioSpec(**scn))
    seed = int(cfg["world_seed"])
    fleet = draw_fleet(seed, cfg["cells"], spec,
                       n_range=(cfg["users_min"], spec.N))
    stream = dynamics.StreamConfig(side_m=spec.side_m, **tr["stream"])
    svc = dict(cfg["service"])
    if "ladder" in svc:
        levels = _entries(cfg, "service", "ladder", CompressionLevel,
                          RUNG_FIELDS)
        svc["ladder"] = _made("service.ladder",
                              lambda: CompressionLadder(levels=levels))
    svc_cfg = ServiceConfig(drift=DriftConfig(**cfg["drift"]), stream=stream,
                            event_rate=tr["event_rate"], **svc)
    return PlanningService(fleet, lam=cfg["lam"],
                           sroa_cfg=sroa.SroaConfig(**cfg["sroa"]),
                           cfg=svc_cfg, spec=spec, seed=seed,
                           devices=devices)


def warm(service, share: float) -> None:
    """Compile what a tick runs: the dynamics step's conversions (on a
    throwaway copy of the world), and the replan buckets a tick can reach:
    every power-of-two bucket of at least ``share`` x C rows, and C itself.
    Each bucket is searched from the deployed plans, so the search stops
    after its first rounds and the deployed state is left as it was."""
    import jax
    import jax.numpy as jnp
    from repro.fleet import batch as fbatch
    from repro.fleet import dynamics

    dynamics.fleet_step(service.fleet, service.state,
                        np.random.default_rng(0), cfg=service.cfg.stream,
                        spec=service.spec)

    C = service.fleet.C
    sizes, b = [], 1
    while b < C:
        if b >= share * C:
            sizes.append(b)
        b <<= 1
    sizes.append(C)
    for b in sizes:
        idx = np.arange(b) % C
        sub = jax.tree.map(lambda x, i=idx: x[jnp.asarray(i)], service.fleet)
        # A re-search with the ladder on starts from the deployed levels.
        kw = ({"init_comps": jnp.asarray(service.comps[idx], jnp.int32)}
              if service._comp_on else {})
        out = service._engine(sub, jnp.asarray(service.assigns[idx]),
                              rows=idx, **kw)
        jax.block_until_ready((out.assign, fbatch.fleet_assignments(sub)))


def capture_searches(service, sink: list) -> None:
    """Keep the inputs and the result of every assignment search."""
    engine = service._engine

    def kept(fleet, init_assigns, *args, **kwargs):
        out = engine(fleet, init_assigns, *args, **kwargs)
        sink.append({"fleet": fleet, "init": init_assigns,
                     "rows": kwargs.get("rows"),
                     "init_comps": kwargs.get("init_comps"), "out": out})
        return out
    service._engine = kept


def plan_table(service) -> dict:
    """The deployed plans after a tick (host copies, device refs for cells);
    with the ladder on, the deployed levels under ``comp``."""
    a = service.alloc
    plan = {"tick": service.tick_idx - 1, "fleet": service.fleet,
            "active": np.asarray(service.state.active, bool).copy(),
            "assign": service.assigns.copy(),
            "b": a.b, "f": a.f, "p": a.p, "t": a.t, "R": a.R,
            "lam": service.lam}
    if service._comp_on:
        plan["comp"] = service.comps.copy()
    return plan


def poisson_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times of an open-loop Poisson stream in [0, seconds)."""
    rng = np.random.default_rng([seed, 0x0E0])
    n = max(16, int(rate * seconds * 2 + 64))
    out = np.cumsum(rng.exponential(1.0 / rate, size=n))
    while out[-1] < seconds:
        out = np.concatenate([out, out[-1] + np.cumsum(
            rng.exponential(1.0 / rate, size=n))])
    return out[out < seconds]


def drive(service, run: Run, spans: Spans, clock: CompileClock,
          trace_dir: str | None = None) -> None:
    """The measured window: ticks back to back under open-loop requests.

    The generator issues requests due in ``[0, seconds)``; the loop ends at
    the first tick end after which every request is answered and at least
    ``cost_ticks`` ticks have run.  The window runs from the start of the
    first tick to the end of the last one.

    With ``trace_dir`` the profiler traces the window's last tick: the first
    that starts once the generator has sent every request, so no request
    waits on the profiler.  The trace is written after the window closes.
    """
    import jax

    tr = run.cell.traffic
    searches: list = []
    capture_searches(service, searches)
    _stamp_on_resolve(service.queue)
    offsets = poisson_offsets(tr["request_rate_per_s"], run.seconds, run.seed)
    lowered0 = clock.lowered
    t0 = time.perf_counter()
    gen = OpenLoop(service.submit, offsets, t0)
    run.window = (t0, t0)
    traced = False
    gen.start()
    while True:
        k = len(run.ticks)
        if (trace_dir is not None and not traced and gen.finished.is_set()
                and k + 1 >= tr["cost_ticks"]):
            # Device ops and the TraceAnnotation spans; no Python tracer,
            # which would record (and slow) every call of the host path.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced = True
        a = time.perf_counter() if k else t0
        n_search = len(searches)
        with jax.profiler.TraceAnnotation("tick"):
            rec = service.tick()
        b = time.perf_counter()
        run.ticks.append(Tick(t0=a, t1=b, replanned=np.asarray(rec.replanned),
                              sum_R=rec.sum_R,
                              rows_searched=sum(len(s["rows"]) for s in
                                                searches[n_search:]),
                              plan=plan_table(service),
                              searches=searches[n_search:]))
        if (gen.finished.is_set() and len(service.queue) == 0
                and len(run.ticks) >= tr["cost_ticks"]
                and (trace_dir is None or traced)):
            break
    run.window = (t0, run.ticks[-1].t1)
    run.compiles_in_window = clock.lowered - lowered0
    gen.join(timeout=60)
    if traced:
        jax.profiler.stop_trace()
        print(f"[bench] traced tick {b - a:.1f} s, trace written after "
              f"{time.perf_counter() - b:.1f} s", file=sys.stderr)
    run.requests = gen.requests
    run.spans = spans.spans
    run.span_targets = spans.targets
    run.cells_C = service.fleet.C
