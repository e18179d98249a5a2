#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell to find its knee; not part of a run.

    python bench/knee.py --workload s3-3mb.read.poisson --rates 50,60,70 --seconds 20

Runs the cell once per rate in one process, the mix's ``rate_per_s``
replaced, and prints one JSON line per rate: the latency percentiles, the
answered rate, and the mean proxy wait of the window's first and last
thirds. The knee is the highest rate whose last third waits no longer than
its first: above it the backlog grows all through the window. A cell's
fixed rate is 4/5 of the knee, written into its mix file by hand.
"""

from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as bench_run  # noqa: E402
from benchlib import manifest, readers, stats  # noqa: E402
from benchlib.harness import CompileCounter  # noqa: E402


def thirds_wait_ms(rec, op: str) -> tuple[float, float]:
    reqs = [r for r in rec.requests if r.op == op and r.first_start is not None]
    span = rec.t_end - rec.t0
    first = [(r.first_start - r.due) * 1e3 for r in reqs if r.due < rec.t0 + span / 3]
    last = [(r.first_start - r.due) * 1e3 for r in reqs if r.due >= rec.t0 + 2 * span / 3]
    return stats.mean(first), stats.mean(last)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, per second")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    traffic = manifest.resolve(args.workload)["traffic"]
    op = traffic["op"]
    compiles = CompileCounter()
    t0 = T_PROC0
    for rate in (float(r) for r in args.rates.split(",")):
        result, rec = bench_run.execute(args.workload, args.seed, args.seconds, False,
                                        traffic=dict(traffic, rate_per_s=rate),
                                        compiles=compiles, t_proc0=t0)
        done = [r.done for r in rec.requests if r.ok and r.done is not None]
        w_first, w_last = thirds_wait_ms(rec, op)
        print(json.dumps({
            "rate_per_s": rate, "correct": result["correct"],
            "p50_ms": readers.latency_pct(rec, op, 50),
            "p99_ms": readers.latency_pct(rec, op, 99),
            "answered_per_s": sum(1 for t in done if t < rec.t_end) / args.seconds,
            "wait_first_third_ms": w_first, "wait_last_third_ms": w_last,
            "gen_late_p99_ms": readers.gen_late_p99_ms(rec, op)}), flush=True)
        t0 = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
