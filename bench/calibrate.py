#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; not part of a run.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5 [--control]

Runs the cell once per seed in one process (compiles are shared) and prints
one JSON line per seed with every checked number. With ``--control`` each
run also reads the control, which has to come out as not correct:

* a served model: the float32 reference with its weights rounded through
  float8 (e4m3, one scale per output column) and bfloat16 activations, in
  place of the program, on the same prompts and served tokens: the widest
  gap of the token it puts first (``control_token_gap``);
* a system that runs no model: the program with one guarantee broken where
  the answer is produced, one byte of every codec output flipped
  (``control`` = that run's checks).
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
from benchlib.harness import CompileCounter  # noqa: E402


class FlipCodec:
    """The program's codec with byte 0 of every item's output flipped."""

    def __init__(self, inner):
        self.inner = inner

    @staticmethod
    def _flip(out):
        import numpy as np

        out = np.array(out, copy=True)
        out.reshape(out.shape[0], -1)[:, 0] ^= 0xFF
        return out

    def encode(self, data, n, k, *, n_out=None):
        return self._flip(self.inner.encode(data, n, k, n_out=n_out))

    def decode(self, rows, present, n, k):
        return self._flip(self.inner.decode(rows, present, n, k))

    def __getattr__(self, name):
        return getattr(self.inner, name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    from benchlib import manifest

    found = manifest.resolve(args.workload)
    storage = found["config"]["driver"] == "storage"
    compiles = CompileCounter()
    t0 = T_PROC0
    for seed in (int(s) for s in args.seeds.split(",")):
        result, rec = bench_run.execute(args.workload, seed, args.seconds, False,
                                        compiles=compiles, t_proc0=t0,
                                        control=args.control and not storage)
        line = {"seed": seed, "correct": result["correct"], "checks": result["checks"],
                "metrics": result["metrics"], "attempted": result["attempted"],
                "control": rec.control}
        if args.control and storage:
            from repro.coding.codec import get_codec

            bad, _ = bench_run.execute(args.workload, seed, args.seconds, False,
                                       compiles=compiles, codec=FlipCodec(get_codec()),
                                       require_chip=True, t_proc0=time.monotonic())
            line["control"] = {"correct": bad["correct"], "checks": bad["checks"]}
        print(json.dumps(line), flush=True)
        t0 = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
