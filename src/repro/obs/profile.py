"""Per-compiled-function launch profiling: cost model vs measured wallclock.

:func:`profile_launch` AOT-compiles one jitted callable at one arg shape,
reads XLA's ``cost_analysis`` (FLOPs, bytes accessed), measures post-warmup
wallclock (best of ``iters`` blocked calls), and derives the roofline view:
achieved GFLOP/s and GB/s, arithmetic intensity, the compute-vs-memory
bound side, and the fraction of the chip's peak achieved.  Peaks come from
:data:`repro.launch.roofline.PEAKS`, keyed by the device's ``device_kind``;
on a device that is not in that table (the CPU included) ``bound`` and
``frac_peak`` are None.

Each profile registers a labeled :class:`repro.obs.compile.CompileStats`
(held strongly here, so the weak registry keeps it), which makes profiled
functions first-class citizens of :func:`repro.obs.compile_snapshot` —
one query answers both "what compiled" and "how fast did it run".
:func:`profile_snapshot` returns the measured records merged with those
counts, and :func:`format_profile` renders the terminal table the demo and
the dashboard embed.
"""
from __future__ import annotations

import time

from repro.obs.trace import span
from repro.obs.compile import CompileStats

#: Strong refs so the weak compile registry keeps profiled labels alive.
_PROFILES: dict[str, dict] = {}
_STATS: dict[str, CompileStats] = {}


def profile_launch(label: str, fn, *args, warmup: int = 1, iters: int = 3,
                   **kwargs) -> dict:
    """Profile one jitted callable at one argument shape; returns the record.

    ``fn`` must be a ``jax.jit`` product (anything with ``.lower``).  The
    compile happens here (AOT), then ``warmup`` discarded calls, then the
    best of ``iters`` blocked calls is the wallclock."""
    import jax

    from repro.launch.roofline import PEAKS

    peaks = PEAKS.get(jax.devices()[0].device_kind)

    with span("obs.profile_compile", label=label):
        compiled = fn.lower(*args, **kwargs).compile()
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", 0.0))

    for _ in range(warmup):
        jax.block_until_ready(compiled(*args, **kwargs))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args, **kwargs))
        best = min(best, time.perf_counter() - t0)

    bound = frac_peak = None
    if peaks is not None and best > 0:
        t_compute = flops / peaks.flops
        t_memory = nbytes / peaks.hbm_bw
        bound = "compute" if t_compute >= t_memory else "memory"
        # Efficiency vs the binding roofline term at the chip's peaks.
        frac_peak = max(t_compute, t_memory) / best
    rec = {
        "label": label,
        "flops": flops,
        "bytes": nbytes,
        "wall_s": best,
        "gflops": flops / best / 1e9 if best > 0 else 0.0,
        "gbps": nbytes / best / 1e9 if best > 0 else 0.0,
        "intensity": flops / nbytes if nbytes else 0.0,
        "bound": bound,
        "frac_peak": frac_peak,
    }
    _PROFILES[label] = rec
    stats = _STATS.get(label)
    if stats is None:
        stats = _STATS[label] = CompileStats(label=f"profile.{label}")
    stats.traces += 1
    stats.launches += warmup + iters
    return rec


def profile_snapshot() -> dict:
    """label -> measured record + the registry's compile counts."""
    out = {}
    for label, rec in _PROFILES.items():
        stats = _STATS.get(label)
        out[label] = dict(rec)
        if stats is not None:
            out[label]["traces"] = stats.traces
            out[label]["launches"] = stats.launches
    return out


def reset_profiles() -> None:
    _PROFILES.clear()
    _STATS.clear()


def _fmt_qty(v: float) -> str:
    for unit, div in (("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if v >= div:
            return f"{v / div:.2f}{unit}"
    return f"{v:.0f}"


def fmt_bound(r: dict) -> tuple[str, str]:
    """(bound, peak %) table cells; "n/a" off the peaks table."""
    if r["frac_peak"] is None:
        return "n/a", "n/a"
    return r["bound"], f"{r['frac_peak'] * 100:.2f}"


def format_profile(snap: dict | None = None) -> str:
    """ASCII roofline/efficiency table over :func:`profile_snapshot`."""
    snap = profile_snapshot() if snap is None else snap
    rows = [("fn", "flops", "bytes", "wall_ms", "gflop/s", "gb/s",
             "bound", "peak%", "launches")]
    for label, r in sorted(snap.items()):
        rows.append((
            label, _fmt_qty(r["flops"]), _fmt_qty(r["bytes"]),
            f"{r['wall_s'] * 1e3:.3f}", f"{r['gflops']:.2f}",
            f"{r['gbps']:.2f}", *fmt_bound(r),
            str(r.get("launches", "")),
        ))
    widths = [max(len(row[i]) for row in rows) for i in range(len(rows[0]))]
    return "\n".join(
        "  ".join(c.ljust(w) for c, w in zip(row, widths)).rstrip()
        for row in rows
    )
