#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it is started on.

    python3 bench/run_cell.py --workload metro.churn --seed 12345 \
        --seconds 51 --trace 0

Builds the cell's fleet from its configuration, bootstraps
``PlanningService`` on one chip, warms the replan buckets the traffic
reaches (set-up), then ticks the control plane back to back for
``--seconds`` of open-loop plan requests drawn from ``--seed`` (the
window).  Afterwards the deployed plans are compared with the plain
reference (``bench/check.py``).  The last line of standard output is the
result as JSON; the numbers compared, each with its limit, are the last
lines of standard error and the last key of that JSON.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, with the last tick of the window traced by the
profiler.  Exits non-zero, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _number(v):
    return v if math.isfinite(v) else str(v)


def execute(cell, seed: int, seconds: float, trace: bool, devices,
            t_start: float = T_START) -> dict:
    """Set up, drive the window, check and reduce: the result's fields.

    ``devices`` are the devices the cell runs on; the caller has checked
    them (the CPU tests call this directly).
    """
    import jax
    from bench import check, harness
    from bench import devtrace
    from repro.runtime import compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compile_cache.enable()
    clock = harness.CompileClock()
    service = harness.build(cell, devices[:cell.chips])
    harness.warm(service, cell.traffic["warm_share"])
    spans = harness.Spans()
    spans.install(service, harness.span_targets())
    run = harness.Run(cell=cell, seed=seed, seconds=seconds)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        harness.drive(service, run, spans, clock, trace_dir)
        run.setup_s = run.window[0] - t_start
        print("[bench] ticks (s, cells replanned, rows searched): "
              + json.dumps([[t.t1 - t.t0, len(t.replanned), t.rows_searched]
                            for t in run.ticks]), file=sys.stderr)
        peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices[:cell.chips]))
        if trace:
            t0 = time.perf_counter()
            run.trace = devtrace.reduce_dir(trace_dir, spans.spans)
            print(f"[bench] trace reduced in {time.perf_counter() - t0:.1f} s",
                  file=sys.stderr)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    gathered = check.gather(run)
    run.release()
    del service
    gc.collect()
    sroa = cell.config["sroa"]
    verdict = check.compare(gathered, (sroa["b_iters"], sroa["f_iters"],
                                       sroa["p_iters"], sroa["t_iters"]),
                            harness.ladder_factors(cell.config))

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = harness.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": verdict["correct"],
           "attempted": len(run.requests),
           "failed": sum(r.done is None for r in run.requests),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["compared"] = {k: {"value": _number(v["value"]), "limit": v["limit"]}
                       for k, v in verdict["numbers"].items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The compile cache lives at a fixed path inside this checkout.
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    # The checkout root in place of this script's directory (whose module
    # names must not shadow others), and the program's sources.
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    try:
        from bench import harness
        cell = harness.resolve(args.workload)
        import repro  # noqa: F401
    except (ImportError, ValueError) as e:
        print(f"[bench] cannot run {args.workload!r}: {e}", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"[bench] needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 3
    out = execute(cell, args.seed, args.seconds, bool(args.trace), devices)
    for k, v in out["compared"].items():
        print(f"[bench] compared {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
