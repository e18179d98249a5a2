"""Perf-regression gate over the ``BENCH_*.json`` artifacts.

Every bench leg writes a ``benchmarks/results/BENCH_*.json`` artifact; this
module normalizes each into a flat list of named metrics and compares them
against the committed baselines in ``benchmarks/baselines/``, failing CI on
regressions. Three metric kinds with different contracts:

* ``count`` — structural integers (grid sizes, compile/launch counts). The
  artifact-level twin of the test-suite compile pins: any drift fails.
* ``stat`` — deterministic simulation outputs (headline ratios, capacity
  estimates, interference spreads). Seeded RNG makes these host-independent,
  so they gate at a tight relative tolerance (default 10%) in BOTH
  directions — an unexplained improvement is as suspicious as a regression.
* ``wallclock`` — req/s, ms, speedups. Shared CI cores make these noisy, so
  they only *warn* past their (generous) tolerance unless
  ``--strict-wallclock``; the direction is inferred from the unit (higher
  req/s and x good, lower ms good).

A results file with no committed baseline passes with a note (so new bench
legs land before their baseline), as do metrics present on only one side of
an ``--update``d schema change — but a metric the baseline has and the new
run lost is a coverage regression and fails.

Usage::

    python benchmarks/gate.py --check benchmarks/results/
    python benchmarks/gate.py --update benchmarks/results/   # refresh baselines
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

BASELINES_DIR = os.path.join(os.path.dirname(__file__), "baselines")

TOL_STAT = 0.10
TOL_WALLCLOCK = 0.50

# Units where larger is better; anything else (ms, s, MB) regresses upward.
_HIGHER_BETTER_UNITS = {"req/s", "x", "ratio"}


def _metric(metrics: dict, name: str, value, kind: str, unit: str = "") -> None:
    if value is None:
        return
    try:
        value = float(value)
    except (TypeError, ValueError):
        return
    metrics[name] = {"value": value, "kind": kind, "unit": unit}


def _frontier_metrics(art: dict, metrics: dict) -> None:
    """Shared normalizer for the fleet/taskq frontier artifact layout."""
    for name in ("grid_size", "count", "compiles", "launches"):
        _metric(metrics, name, art.get(name), "count")
    for pol, cap in (art.get("capacity_req_s") or {}).items():
        _metric(metrics, f"capacity_req_s/{pol}", cap, "stat", "req/s")
    head = art.get("headline") or {}
    for name in ("delay_gain_vs_basic", "capacity_gain_vs_latency_optimal",
                 "tofec_light_mean", "basic_light_mean"):
        _metric(metrics, f"headline/{name}", head.get(name), "stat")
    # Flight-recorder zoom (taskq only): structural counts of the replayed
    # cell's per-request records — drift means the recorder lost coverage.
    flight = art.get("flight") or {}
    for name in ("requests", "records", "exemplars"):
        _metric(metrics, f"flight/{name}", flight.get(name), "count")


def _multiclass_metrics(art: dict, metrics: dict) -> None:
    for name in ("grid_size", "count", "compiles", "launches"):
        _metric(metrics, name, art.get(name), "count")
    for disc, entry in (art.get("interference") or {}).items():
        _metric(metrics, f"interference/{disc}/jain_delay",
                entry.get("jain_delay"), "stat")
        _metric(metrics, f"interference/{disc}/p99_spread",
                entry.get("p99_spread"), "stat")


def _shard_metrics(art: dict, metrics: dict) -> None:
    for name in ("grid", "count", "big_grid", "big_count"):
        _metric(metrics, name, art.get(name), "count")
    _metric(metrics, "baseline_materialized_ms",
            art.get("baseline_materialized_ms"), "wallclock", "ms")
    _metric(metrics, "big_grid_ms", art.get("big_grid_ms"), "wallclock", "ms")
    for row in art.get("scaling") or []:
        _metric(metrics, f"d{row.get('devices')}/ms", row.get("ms"),
                "wallclock", "ms")


_NORMALIZERS = {
    "repro.fleet/BENCH_fleet": _frontier_metrics,
    "repro.taskq/BENCH_taskq": _frontier_metrics,
    "repro.sched/BENCH_multiclass": _multiclass_metrics,
    "repro.fleet/BENCH_shard": _shard_metrics,
}


def normalize(artifact: dict) -> dict:
    """Artifact dict → ``{name: {value, kind, unit}}`` flat metric map.

    Unknown schemas normalize to the empty map (pass-through) so a new
    artifact can land before the gate learns to read it.
    """
    schema = str(artifact.get("schema", ""))
    fn = _NORMALIZERS.get(schema.rsplit("/", 1)[0])
    metrics: dict = {}
    if fn is not None:
        fn(artifact, metrics)
    return metrics


def _regresses(name: str, base: dict, new: dict,
               tol_stat: float, tol_wc: float):
    """Compare one metric; returns (level, message) or None.

    ``level`` is ``"fail"`` or ``"warn"``.
    """
    bv, nv = base["value"], new["value"]
    kind = base.get("kind", new.get("kind", "stat"))
    if kind == "count":
        if nv != bv:
            return "fail", f"{name}: count {bv:g} -> {nv:g}"
        return None
    denom = abs(bv) if bv else 1.0
    rel = (nv - bv) / denom
    if kind == "stat":
        if abs(rel) > tol_stat:
            return "fail", (f"{name}: {bv:.4g} -> {nv:.4g} "
                            f"({rel:+.1%}, tol ±{tol_stat:.0%})")
        return None
    # wallclock: regression direction from the unit
    worse = -rel if base.get("unit") in _HIGHER_BETTER_UNITS else rel
    if worse > tol_wc:
        return "warn", (f"{name}: {bv:.4g} -> {nv:.4g} {base.get('unit', '')} "
                        f"({worse:+.1%} worse, tol {tol_wc:.0%})")
    return None


def check_file(result_path: str, baseline_path: str, *,
               tol_stat: float = TOL_STAT, tol_wc: float = TOL_WALLCLOCK):
    """Gate one artifact; returns (fails, warns, notes) message lists."""
    fails: list = []
    warns: list = []
    notes: list = []
    with open(result_path) as f:
        new = normalize(json.load(f))
    if not os.path.exists(baseline_path):
        notes.append(f"no baseline for {os.path.basename(result_path)} (pass)")
        return fails, warns, notes
    with open(baseline_path) as f:
        base = json.load(f).get("metrics", {})
    for name, bm in sorted(base.items()):
        nm = new.get(name)
        if nm is None:
            if bm.get("kind") == "wallclock":
                warns.append(f"{name}: wallclock metric missing from new run")
            else:
                fails.append(f"{name}: metric missing from new run")
            continue
        hit = _regresses(name, bm, nm, tol_stat, tol_wc)
        if hit is not None:
            (fails if hit[0] == "fail" else warns).append(hit[1])
    for name in sorted(set(new) - set(base)):
        notes.append(f"{name}: new metric, no baseline (pass)")
    return fails, warns, notes


def _result_files(results_dir: str) -> list:
    return sorted(glob.glob(os.path.join(results_dir, "BENCH_*.json")))


def update(results_dir: str, baselines_dir: str) -> list:
    """Rewrite the committed baselines from a results directory."""
    os.makedirs(baselines_dir, exist_ok=True)
    written = []
    for path in _result_files(results_dir):
        with open(path) as f:
            art = json.load(f)
        metrics = normalize(art)
        if not metrics:
            continue
        out = {
            "schema": art.get("schema"),
            "meta": art.get("meta"),
            "metrics": metrics,
        }
        dst = os.path.join(baselines_dir, os.path.basename(path))
        with open(dst, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        written.append(dst)
    return written


def check(results_dir: str, baselines_dir: str, *,
          tol_stat: float = TOL_STAT, tol_wc: float = TOL_WALLCLOCK,
          strict_wallclock: bool = False) -> int:
    """Gate every artifact in ``results_dir``; returns the exit code."""
    paths = _result_files(results_dir)
    if not paths:
        print(f"gate: no BENCH_*.json under {results_dir} (nothing to check)")
        return 0
    n_fail = 0
    for path in paths:
        name = os.path.basename(path)
        fails, warns, notes = check_file(
            path, os.path.join(baselines_dir, name),
            tol_stat=tol_stat, tol_wc=tol_wc,
        )
        if strict_wallclock:
            fails, warns = fails + warns, []
        status = "FAIL" if fails else "ok"
        print(f"gate: {name}: {status} "
              f"({len(fails)} fail, {len(warns)} warn, {len(notes)} note)")
        for msg in fails:
            print(f"  FAIL {msg}")
        for msg in warns:
            print(f"  warn {msg}")
        for msg in notes:
            print(f"  note {msg}")
        n_fail += len(fails)
    if n_fail:
        print(f"gate: {n_fail} regression(s); refresh intended changes with "
              f"`python benchmarks/gate.py --update <results-dir>`")
        return 1
    print("gate: all artifacts within tolerance")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--check", metavar="DIR",
                      help="gate a results directory against the baselines")
    mode.add_argument("--update", metavar="DIR",
                      help="rewrite the baselines from a results directory")
    ap.add_argument("--baselines", default=BASELINES_DIR,
                    help="baseline directory (default: benchmarks/baselines)")
    ap.add_argument("--tol-stat", type=float, default=TOL_STAT)
    ap.add_argument("--tol-wallclock", type=float, default=TOL_WALLCLOCK)
    ap.add_argument("--strict-wallclock", action="store_true",
                    help="promote wallclock warnings to failures")
    args = ap.parse_args(argv)
    if args.update:
        for dst in update(args.update, args.baselines):
            print(f"gate: wrote {dst}")
        return 0
    return check(args.check, args.baselines, tol_stat=args.tol_stat,
                 tol_wc=args.tol_wallclock,
                 strict_wallclock=args.strict_wallclock)


if __name__ == "__main__":
    sys.exit(main())
