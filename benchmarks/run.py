"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  fig2  — SROA vs RA baselines (FDMA/OFDMA)        [bench_sroa]
  fig3  — lambda sweep SROA/HFEL/FEDL              [bench_lambda]
  fig4/5 — TSIA vs UA baselines + move trace       [bench_tsia]
  fig6  — TSIA convergence vs N, M                 [bench_convergence]
  fig7/8 — HFL vs FL accuracy + objective          [bench_hfl_vs_fl]
  roofline — per-cell terms from the dry-run       [roofline]
  fleet — batched vs looped SROA + batched TSIA    [bench_fleet]
  engine — device-resident assignment engine       [bench_engine]
  serve — streaming control plane under load       [bench_serve]
  horizon — rolling-horizon (MPC) vs snapshot      [bench_horizon]
  hetero — device tiers + compression vs blind     [bench_hetero]
  topology — designed edge placement vs uniform    [bench_topology]

``--json PATH`` additionally writes every row as structured JSON — with
run metadata (git rev, jax version, backend/device, timestamp) — so
``BENCH_*.json`` perf trajectories are comparable across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

# Make `python benchmarks/run.py` work from any cwd without PYTHONPATH:
# the suite modules import as `benchmarks.*` and the package as `repro.*`.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _parse_row(suite: str, line: str) -> dict:
    """CSV row -> JSON record; `derived` k=v pairs become typed fields.

    Suites encode structured metrics as ``k=v`` pairs separated by ``;``
    (e.g. ``R=123.4;rounds=7;score_flops=2.1e9``), so the ``--json``
    payload exposes candidate-scoring FLOPs, trip counts etc. as real
    columns instead of an opaque string.  Non-numeric values stay strings;
    rows without pairs just omit ``fields``.
    """
    name, us, derived = line.split(",", 2)
    rec = {"suite": suite, "name": name, "us_per_call": float(us),
           "derived": derived}
    fields = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            fields[k.strip()] = float(v)
        except ValueError:
            fields[k.strip()] = v
    if fields:
        rec["fields"] = fields
    return rec


def _run_metadata() -> dict:
    """Environment fingerprint embedded in every ``--json`` payload.

    Makes BENCH_*.json trajectories comparable across PRs: a regression is
    only a regression when the backend, device, and jax version match.
    """
    import platform
    import subprocess

    import jax

    try:
        rev = subprocess.run(
            ["git", "-C", _ROOT, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        rev = ""
    dev = jax.devices()[0]
    return {
        "git_rev": rev or "unknown",
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "device_count": jax.device_count(),
        "python": platform.python_version(),
        "platform": platform.platform(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: sroa,lambda,tsia,convergence,"
                         "hfl_vs_fl,roofline,fleet,engine,serve,horizon,"
                         "hetero,topology")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as JSON to PATH")
    args = ap.parse_args()
    from repro.runtime import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_convergence, bench_engine, bench_fleet,
                            bench_hetero, bench_hfl_vs_fl, bench_horizon,
                            bench_lambda, bench_serve, bench_sroa,
                            bench_topology, bench_tsia, roofline)
    suites = {
        "sroa": bench_sroa.run,
        "lambda": bench_lambda.run,
        "tsia": lambda: bench_tsia.run(trace=True),
        "convergence": bench_convergence.run,
        "hfl_vs_fl": bench_hfl_vs_fl.run,
        "roofline": roofline.run,
        "fleet": bench_fleet.run,
        "engine": bench_engine.run,
        "serve": bench_serve.run,
        "horizon": bench_horizon.run,
        "hetero": bench_hetero.run,
        "topology": bench_topology.run,
    }
    wanted = args.only.split(",") if args.only else list(suites)
    unknown = [w for w in wanted if w not in suites]
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; choose from "
                 f"{sorted(suites)}")
    if args.json:
        try:
            with open(args.json, "w"):  # fail on an unwritable path now,
                pass                    # not after a long benchmark run
        except OSError as e:
            ap.error(f"--json {args.json}: {e}")
    print("name,us_per_call,derived")
    failed = False
    records = []
    for name in wanted:
        try:
            for line in suites[name]():
                print(line, flush=True)
                records.append(_parse_row(name, line))
        except Exception:   # noqa: BLE001 — report and continue
            failed = True
            print(f"{name},0.0,SUITE-ERROR", flush=True)
            records.append({"suite": name, "name": name, "us_per_call": 0.0,
                            "derived": "SUITE-ERROR"})
            traceback.print_exc(file=sys.stderr)
    if args.json:
        meta = _run_metadata()
        payload = {
            # Kept at the top level for backwards compatibility with the
            # PR 1 payload shape; `metadata` is the complete fingerprint.
            "timestamp": meta["timestamp"],
            "backend": meta["backend"],
            "metadata": meta,
            "suites": wanted,
            "ok": not failed,
            "rows": records,
        }
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"# wrote {len(records)} rows to {args.json}", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
