#!/usr/bin/env python3
"""One run of one benchmark cell, on the machine it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix
and metric readers are found by name (``benchlib.manifest``), and the
configuration's ``driver`` key names the driver that runs it. Set-up makes
everything from ``--seed``, warms every shape the mix uses and counts as
``setup_s``; the window then runs for ``--seconds``; after it, the check
compares what the timed path produced with a plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown`` of the device trace, and last ``checks``: each
number compared, beside its limit. The checks are also the last lines of
standard error.

A run that finds no TPU, or fewer chips than the cell asks for, exits with
code 3 and prints no result. So does one whose codec is not the compiled
Pallas kernel (interpret mode is refused).
"""

from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
# The program's own metric buffers swap in another serving launch; both sides
# of every comparison run the plain one.
os.environ.pop("REPRO_OBS", None)

from benchlib import manifest  # noqa: E402
from benchlib.harness import CompileCounter, Ctx, NoChip, device_gate  # noqa: E402
from benchlib.harness import enable_compile_cache  # noqa: E402

EXIT_NO_CHIP = 3


def check_codec(codec) -> None:
    """A measurement run drives the compiled Pallas kernel, never the interpreter."""
    interpret = getattr(codec.backend, "interpret", None)
    if codec.name != "pallas" or interpret is not False:
        raise NoChip(f"the codec is {codec.name!r} (interpret={interpret}); "
                     "a measurement run needs the compiled Pallas kernel")


def metric_values(rec, entries: list[dict], bench_dir: Path) -> dict:
    out = {}
    for m in entries:
        v = manifest.metric_reader(m["name"], bench_dir)(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            root: Path = ROOT, require_chip: bool = True, codec=None,
            config: dict | None = None, traffic: dict | None = None,
            compiles: CompileCounter | None = None, control: bool = False,
            t_proc0: float | None = None, cache: bool = True):
    """One run; returns (result dict, RunRecord). Tests pass ``require_chip``
    False, their own ``codec`` and small ``config`` / ``traffic``."""
    found = manifest.resolve(workload, root)
    cell = found["cell"]
    config = config or found["config"]
    traffic = traffic or found["traffic"]
    import jax

    if require_chip:
        devices = device_gate(int(cell["chips"]))
    else:
        devices = jax.devices()[: int(cell["chips"])]
    if cache:
        print(f"compile cache: {enable_compile_cache()}", flush=True)
    if require_chip:
        from repro.coding.codec import get_codec

        check_codec(codec or get_codec())
    from benchlib.peaks import peaks_for

    peaks = peaks_for(devices[0].device_kind) if require_chip else None
    drv = manifest.driver(config["driver"], found["bench_dir"])
    ctx = Ctx(workload=workload, seed=seed, seconds=seconds, trace=trace, config=config,
              traffic=traffic, t_proc0=T_PROC0 if t_proc0 is None else t_proc0,
              codec=codec, compiles=compiles or CompileCounter(), devices=devices,
              control=control)
    rec = drv.run(ctx)
    rec.peaks = peaks
    d0 = devices[0]
    print(f"compiles inside the window: {rec.compiles_in_window}", flush=True)
    entries = manifest.cell_metrics(found["manifest"], workload, trace)
    result = {
        "correct": all(c.ok for c in rec.checks) and bool(rec.checks),
        "attempted": rec.attempted,
        "failed": rec.failed,
        "metrics": metric_values(rec, entries, found["bench_dir"]),
        "device": {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
                   "memory_peak_bytes": rec.memory_peak_bytes},
    }
    if trace and rec.trace is not None:
        result["device"]["busy_s"] = rec.trace.busy_s
        result["device"]["window_s"] = rec.trace.window_s
        result["breakdown"] = rec.trace.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in rec.checks}
    return result, rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, _ = execute(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return EXIT_NO_CHIP
    line = json.dumps(result)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
