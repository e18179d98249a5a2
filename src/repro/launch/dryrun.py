import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

MUST be run as its own process (``python -m repro.launch.dryrun``): the two
lines above execute before any jax import so the 512 placeholder host
devices exist before jax locks the device count. Smoke tests and benches
never import this module.

Per cell it prints/records:
  * compiled.memory_analysis()  — per-device bytes (proves it fits),
  * compiled.cost_analysis()    — FLOPs / bytes for §Roofline,
  * collective bytes parsed from optimized HLO,
  * the three roofline terms + dominant bottleneck.

Results accumulate in benchmarks/results/dryrun/<cell>.json so the roofline
table in EXPERIMENTS.md regenerates from artifacts.
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import analyze, model_flops  # noqa: E402
from repro.launch.specs import dryrun_target, flops_pass_cfg, slstm_flops_correction  # noqa: E402
from repro.models.config import SHAPES, cell_is_runnable  # noqa: E402
from repro.models.registry import arch_names, get  # noqa: E402
from repro.models.sharding import axis_rules  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../../benchmarks/results/dryrun")

# Cache: global FLOPs per (arch, shape) — mesh-independent, computed once.
_FLOPS_CACHE: dict[tuple[str, str], float] = {}


def global_flops(arch_name: str, shape_name: str) -> float:
    """True executed FLOPs: unsharded lowering with scans unrolled.

    XLA's cost analysis counts while-loop bodies ONCE (validated in
    tests/test_dryrun_small.py), so the sharded/scanned compile pass
    undercounts by the trip counts. This pass unrolls every scan (except
    the sLSTM per-token scan — corrected analytically) and reads
    lowered.cost_analysis() without compiling.
    """
    key = (arch_name, shape_name)
    if key in _FLOPS_CACHE:
        return _FLOPS_CACHE[key]
    cfg = get(arch_name).cfg
    shape = SHAPES[shape_name]
    fcfg = flops_pass_cfg(cfg, shape)
    jfn, args = dryrun_target(arch_name, shape_name, None, cfg_override=fcfg)
    lowered = jfn.lower(*args)
    ca = lowered.cost_analysis()
    flops = float(ca.get("flops", 0.0)) + slstm_flops_correction(cfg, shape)
    _FLOPS_CACHE[key] = flops
    return flops


def run_cell(arch_name: str, shape_name: str, multi_pod: bool, *, save: bool = True,
             optimized: bool = False) -> dict:
    import dataclasses as _dc

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    shape = SHAPES[shape_name]
    cfg = get(arch_name).cfg
    cfg_override = None
    if optimized:
        # §Perf beyond-paper levers (EXPERIMENTS.md §Perf): weight gathering,
        # 256-way decode-cache sharding, and pure-DP for sub-1B models.
        accum = 8 if cfg.param_count_dense() > 1e11 else 1
        cfg_override = _dc.replace(
            cfg, weight_gather=True, decode_cache_seq_shard=True,
            grad_accum=accum,
        )
        cfg = cfg_override
    ok, reason = cell_is_runnable(cfg, shape)
    tag = f"{arch_name}×{shape_name}×{'multi' if multi_pod else 'single'}{'×opt' if optimized else ''}"
    rec = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": ("2x16x16" if multi_pod else "16x16") + ("-opt" if optimized else ""),
        "chips": chips,
        "kind": shape.kind,
        "optimized": optimized,
    }
    if not ok:
        rec.update(status="skipped", reason=reason)
        print(f"[dryrun] {tag}: SKIP ({reason})")
        _save(rec, save)
        return rec

    t0 = time.monotonic()
    try:
        with mesh:
            with axis_rules(mesh):
                jfn, args = dryrun_target(arch_name, shape_name, mesh, cfg_override=cfg_override)
                lowered = jfn.lower(*args)
                t_lower = time.monotonic() - t0
                compiled = lowered.compile()
                t_compile = time.monotonic() - t0 - t_lower
                mem = compiled.memory_analysis()
                print(f"[dryrun] {tag}: memory_analysis:")
                print(f"    {mem}")
                ca = compiled.cost_analysis()
                print(f"[dryrun] {tag}: cost_analysis(per-device, loops-once): "
                      f"flops={ca.get('flops', 0):.3e} "
                      f"bytes={ca.get('bytes accessed', 0):.3e}")
                roof = analyze(compiled, chips)
        # True executed FLOPs from the unrolled unsharded lowering.
        roof.flops = global_flops(arch_name, shape_name)
        # HBM traffic: per-device bytes from the compiled artifact undercount
        # loop bodies the same way; scale by the flops correction ratio.
        ca_flops = float(ca.get("flops", 0.0)) * chips
        scale = max((roof.flops / ca_flops) if ca_flops > 0 else 1.0, 1.0)
        roof.hbm_bytes *= chips * scale
        # Scale ONLY loop-resident collectives by the trip-count correction;
        # entry-level ones (grad all-reduce, FSDP epilogues) run once.
        in_loop = roof.coll_breakdown.get("in_loop", 0)
        in_entry = roof.coll_breakdown.get("in_entry", 0)
        roof.coll_bytes = float(in_loop) * scale + float(in_entry)
        mf = model_flops(cfg, shape, shape.kind)
        rec.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            roofline=roof.as_dict(),
            model_flops=mf,
            useful_flops_ratio=(mf / roof.flops) if roof.flops else None,
            memory={
                "argument_size_b": getattr(mem, "argument_size_in_bytes", None),
                "output_size_b": getattr(mem, "output_size_in_bytes", None),
                "temp_size_b": getattr(mem, "temp_size_in_bytes", None),
                "peak_b": getattr(mem, "peak_memory_in_bytes", None),
            },
        )
        print(
            f"[dryrun] {tag}: OK  t_comp={roof.t_compute:.4f}s "
            f"t_mem={roof.t_memory:.4f}s t_coll={roof.t_collective:.4f}s "
            f"dominant={roof.dominant} (lower {t_lower:.0f}s compile {t_compile:.0f}s)"
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}")
        print(f"[dryrun] {tag}: ERROR {type(e).__name__}: {e}")
        traceback.print_exc()
    _save(rec, save)
    return rec


def _save(rec: dict, save: bool):
    if not save:
        return
    os.makedirs(RESULTS_DIR, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh'].replace('x', '_')}.json"
    with open(os.path.join(RESULTS_DIR, name), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--no-save", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="apply §Perf levers (weight_gather, decode cache sharding)")
    args = ap.parse_args()

    archs = arch_names() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, save=not args.no_save, optimized=args.opt)
                n_ok += rec["status"] == "ok"
                n_skip += rec["status"] == "skipped"
                n_err += rec["status"] == "error"
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
