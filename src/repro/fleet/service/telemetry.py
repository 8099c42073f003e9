"""Serving telemetry: plans/sec, replan fraction, tail latency, drift.

One :class:`Telemetry` instance rides with a
:class:`~repro.fleet.service.control.PlanningService`; the control loop
feeds it per-tick and per-request records and :meth:`snapshot` reduces
them to the JSON record `bench_serve` and `serve --mode plan` emit.

Throughput is counted two ways:

* ``plans_per_s``   — cell-plans kept fresh per wall second
  (``C x ticks / elapsed``): every tick re-prices every cell's plan under
  the new channel (cheap batched SROA) and selectively re-searches the
  drifted ones, so each tick delivers a valid, current plan for all C
  cells.  This is the control plane's capacity metric.
* ``requests_per_s`` — plan requests answered per wall second (requests
  coalesce per tick, so this tracks offered load, not capacity).

Spans and counters say where a tick's time goes.  :meth:`Telemetry.span`
times a block on the host clock (``time.perf_counter``) and opens a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
shows the span beside the device ops on one clock; :meth:`Telemetry.count`
adds to a counter.  Inside :meth:`Telemetry.tick` both land in the tick's
record, :attr:`Telemetry.last_tick`, and fold into running sums when the
tick ends; a span closed outside any tick (the bootstrap) is kept apart in
:attr:`Telemetry.setup_ms`, which :meth:`reset` leaves alone.  Spans are
recorded by the thread that runs the ticks.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import time

import jax
import numpy as np

# Drift histogram bin edges.  The leading -inf edge is an underflow bin:
# objective drift is signed (a replanned cell can land BELOW its reference
# R, giving a negative score) and a histogram starting at 0.0 would silently
# drop those ticks — every recorded score must land in some bin, so the
# histogram total stays equal to the number of scores fed in.
DRIFT_BINS = (-np.inf, 0.0, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0,
              np.inf)

# Ticks whose per-span totals the span percentiles are taken over: means
# come from running sums, so a long-running service keeps bounded memory.
SPAN_WINDOW = 1024


@dataclasses.dataclass
class TickSpans:
    """One tick's raw record on the host clock (``time.perf_counter`` s).

    ``spans`` holds ``(name, start, end, parent)`` for every span closed in
    the tick, ``parent`` being the name of the span it was opened in (None
    at the top level); ``counters`` what the tick added to each counter.
    """

    t0: float
    t1: float | None = None
    spans: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)


class Telemetry:
    """Rolling counters for the planning control plane."""

    def __init__(self, drift_bins: tuple = DRIFT_BINS):
        self.drift_bins = np.asarray(drift_bins, np.float64)
        self.setup_ms: dict[str, float] = {}   # spans closed outside ticks
        self._open: list[str] = []             # names of the open spans
        self.reset()

    def reset(self) -> None:
        """Start a fresh measurement window (e.g. after warm-up)."""
        self.t0 = time.perf_counter()
        self.ticks = 0
        self.cells = 0                # C summed over ticks
        self.cells_replanned = 0
        self.cells_changed = 0
        self.engine_calls = 0         # assignment-search (engine) calls
        self.alloc_calls = 0          # batched SROA re-pricing calls
        self.requests = 0             # submitted
        self.served = 0               # answered
        self.coalesced_max = 0        # largest single-call request group
        self.objective_sum = 0.0      # repriced sum R accumulated over ticks
        self.handovers = 0            # active users whose edge changed
        self.latencies_ms: list[float] = []
        self.tick_ms: list[float] = []
        self.drift_hist = np.zeros(len(self.drift_bins) - 1, np.int64)
        self.objective_hist = np.zeros(len(self.drift_bins) - 1, np.int64)
        # D11 heterogeneity counters: users re-searched per device tier
        # (summed over ticks) and the deployed compression-level mix of
        # the LAST tick (a histogram of levels, not a rolling sum — the
        # mix is a state, not a rate).
        self.tier_replans: dict[int, int] = {}
        self.comp_hist: dict[int, int] = {}
        # Spans and counters: the last tick's raw record, and per name the
        # summed milliseconds over ticks plus the recent per-tick totals.
        self.last_tick: TickSpans | None = None
        self._in_tick = False
        self.span_ticks = 0
        self.span_sum_ms: dict[str, float] = {}
        self.span_recent: collections.deque = collections.deque(
            maxlen=SPAN_WINDOW)
        self.counters: dict[str, int] = {}

    # ----------------------------------------------------- spans / counters
    @contextlib.contextmanager
    def tick(self):
        """Open a tick's record; its spans and counters land in
        :attr:`last_tick`, and fold into the running sums when it ends."""
        self.last_tick = TickSpans(t0=time.perf_counter())
        self._in_tick = True
        try:
            yield self.last_tick
        finally:
            rec = self.last_tick
            rec.t1 = time.perf_counter()
            self._in_tick = False
            per_tick: dict[str, float] = {}
            for name, a, b, _ in rec.spans:
                per_tick[name] = per_tick.get(name, 0.0) + (b - a) * 1e3
            for name, ms in per_tick.items():
                self.span_sum_ms[name] = self.span_sum_ms.get(name, 0.0) + ms
            self.span_recent.append(per_tick)
            self.span_ticks += 1

    def tick_elapsed_ms(self) -> float:
        """Milliseconds since the open tick began."""
        return (time.perf_counter() - self.last_tick.t0) * 1e3

    @contextlib.contextmanager
    def span(self, name: str):
        """Time the block on the host clock, under ``name`` in the profiler
        trace too.  Adds no device sync: a span ends where its block does."""
        parent = self._open[-1] if self._open else None
        self._open.append(name)
        a = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            b = time.perf_counter()
            self._open.pop()
            if self._in_tick:
                self.last_tick.spans.append((name, a, b, parent))
            else:
                self.setup_ms[name] = (self.setup_ms.get(name, 0.0)
                                       + (b - a) * 1e3)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to counter ``name`` (and to the open tick's record)."""
        n = int(n)
        self.counters[name] = self.counters.get(name, 0) + n
        if self._in_tick:
            rec = self.last_tick.counters
            rec[name] = rec.get(name, 0) + n

    # ------------------------------------------------------------- recording
    def record_request(self, latency_ms: float) -> None:
        self.served += 1
        self.latencies_ms.append(float(latency_ms))

    def record_tick(self, n_cells: int, n_changed: int, n_replanned: int,
                    engine_calls: int, alloc_calls: int, sum_R: float,
                    tick_ms: float, drift_scores=None,
                    objective_scores=None, coalesced: int = 0,
                    handovers: int = 0, tier_replans=None,
                    comp_levels=None) -> None:
        self.ticks += 1
        self.cells += int(n_cells)
        self.cells_changed += int(n_changed)
        self.cells_replanned += int(n_replanned)
        self.engine_calls += int(engine_calls)
        self.alloc_calls += int(alloc_calls)
        self.objective_sum += float(sum_R)
        self.handovers += int(handovers)
        self.tick_ms.append(float(tick_ms))
        self.coalesced_max = max(self.coalesced_max, int(coalesced))
        if drift_scores is not None:
            hist, _ = np.histogram(np.asarray(drift_scores, np.float64),
                                   bins=self.drift_bins)
            self.drift_hist += hist
        if objective_scores is not None:
            hist, _ = np.histogram(np.asarray(objective_scores, np.float64),
                                   bins=self.drift_bins)
            self.objective_hist += hist
        if tier_replans is not None:
            # flat array of tier ids, one per re-searched user this tick
            tiers, counts = np.unique(
                np.asarray(tier_replans, np.int64), return_counts=True)
            for t, n in zip(tiers, counts):
                self.tier_replans[int(t)] = (self.tier_replans.get(int(t), 0)
                                             + int(n))
        if comp_levels is not None:
            # flat array of deployed levels over active users (replaces the
            # previous mix: the deployed state, not an accumulation)
            lvls, counts = np.unique(
                np.asarray(comp_levels, np.int64), return_counts=True)
            self.comp_hist = {int(lv): int(n)
                              for lv, n in zip(lvls, counts)}

    # ------------------------------------------------------------- reporting
    @staticmethod
    def _pct(xs: list[float], q: float) -> float:
        return float(np.percentile(np.asarray(xs), q)) if xs else 0.0

    def _hist_dict(self, counts: np.ndarray) -> dict:
        return {f"<{hi:g}": int(n)
                for hi, n in zip(self.drift_bins[1:], counts)}

    def _span_stats(self) -> dict:
        """Per span name: mean, p50 and p99 of its milliseconds per tick."""
        out = {}
        for name in sorted(self.span_sum_ms):
            recent = [t.get(name, 0.0) for t in self.span_recent]
            out[name] = {"mean": self.span_sum_ms[name] / self.span_ticks,
                         "p50": self._pct(recent, 50),
                         "p99": self._pct(recent, 99)}
        return out

    def snapshot(self) -> dict:
        elapsed = max(time.perf_counter() - self.t0, 1e-9)
        lat = self.latencies_ms
        return {
            "elapsed_s": elapsed,
            "ticks": self.ticks,
            "plans_per_s": self.cells / elapsed,
            "requests_per_s": self.served / elapsed,
            "requests_served": self.served,
            "replan_fraction": (self.cells_replanned / self.cells
                                if self.cells else 0.0),
            "changed_fraction": (self.cells_changed / self.cells
                                 if self.cells else 0.0),
            "engine_calls": self.engine_calls,
            "alloc_calls": self.alloc_calls,
            "coalesced_max": self.coalesced_max,
            "objective_sum": self.objective_sum,
            "handovers": self.handovers,
            "latency_ms": {"p50": self._pct(lat, 50),
                           "p99": self._pct(lat, 99),
                           "max": max(lat) if lat else 0.0},
            "tick_ms": {"p50": self._pct(self.tick_ms, 50),
                        "p99": self._pct(self.tick_ms, 99)},
            "drift_hist": self._hist_dict(self.drift_hist),
            "objective_drift_hist": self._hist_dict(self.objective_hist),
            # string keys so the record JSON round-trips losslessly
            "per_tier_replans": {str(t): n for t, n
                                 in sorted(self.tier_replans.items())},
            "compression_hist": {str(lv): n for lv, n
                                 in sorted(self.comp_hist.items())},
            # ms per tick of each span; counters summed over the window;
            # spans closed outside ticks (the bootstrap), in ms
            "spans": self._span_stats(),
            "counters": dict(sorted(self.counters.items())),
            "setup_ms": dict(sorted(self.setup_ms.items())),
        }

    def emit(self, fh=None) -> str:
        """The JSON telemetry record (optionally written to ``fh``)."""
        line = json.dumps(self.snapshot())
        if fh is not None:
            fh.write(line + "\n")
        return line
