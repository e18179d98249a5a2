"""Benchmark entrypoint: ``PYTHONPATH=src python -m benchmarks.run``.

One function per paper table/figure (Fig.1, 4-10) plus kernel micro-
benchmarks. Prints ``name,us_per_call,derived`` CSV lines; per-figure data
artifacts land in benchmarks/results/*.csv. The dry-run/roofline tables are
separate (python -m repro.launch.dryrun; see EXPERIMENTS.md).
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring filter on bench names")
    ap.add_argument("--fast", action="store_true", help="reduced request counts")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import kernel_bench, paper_figures

    benches = list(paper_figures.ALL_FIGS) + list(kernel_bench.ALL_KERNEL)
    print("name,us_per_call,derived")
    failures = 0
    for fn in benches:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            kwargs = {}
            if args.fast and "count" in fn.__code__.co_varnames:
                kwargs["count"] = 1200
            for line in fn(**kwargs):
                print(line)
                sys.stdout.flush()
        except Exception as e:
            failures += 1
            print(f"{fn.__name__},ERROR,{type(e).__name__}:{e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
