"""Kernel micro-benchmarks: GF(2) bit-matrix RS encode (Pallas)
vs the table-based GF(256) jnp oracle, plus the unified codec engine's
batched-throughput sweep (backend × batch × (n, k)).

On CPU the Pallas kernel runs in interpret mode, so wall-clock here measures
the *reference environment*, not TPU perf — the TPU story is the §Roofline
arithmetic-intensity argument (bit-matrix matmul is MXU-shaped; table
lookups are not). We report both wall time and derived arithmetic intensity.

The codec sweep is the measurement behind the TOFEC amortization claim
(coding overhead Ψ caps throughput under load, FAST CLOUD §IV): one batched
``Codec.encode`` over b queued objects vs b per-object calls. Rows report
MB/s for each and the batched/looped speedup.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BenchTimer
from repro import obs as _obs
from repro.coding import rs
from repro.coding.codec import Codec
from repro.core import PAPER_READ_3MB, RequestClass, TOFECPolicy
from repro.kernels.gf2mm import gf2mm, ops, ref


def bench_gf2mm(n: int = 12, k: int = 6, B: int = 16384) -> list[str]:
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(k, B), dtype=np.uint8)
    jdata = jnp.asarray(data)

    # jit the wrapper so both timed paths measure pure device dispatch
    enc = jax.jit(lambda d: ops.rs_encode(d, n=n, k=k))
    enc(jdata).block_until_ready()
    with BenchTimer("kernel_rs_encode_pallas", calls=3) as t1:
        for _ in range(3):
            enc(jdata).block_until_ready()

    par = jnp.asarray(rs.cauchy_parity_matrix(n, k))
    ref_fn = jax.jit(lambda d: ref.gf256_matmul_ref(par, d))
    ref_fn(jdata).block_until_ready()
    with BenchTimer("kernel_rs_encode_tableref", calls=3) as t2:
        for _ in range(3):
            ref_fn(jdata).block_until_ready()

    # Derived: GF(2) matmul arithmetic intensity on TPU for this shape.
    M, K = 8 * (n - k), 8 * k
    flops = 2 * M * K * B  # MXU MACs on bit-planes
    bytes_ = (M * K + K * B + M * B)  # bf16→1B-ish planes; order of magnitude
    return [
        t1.row(f"payload={k * B / 2 ** 20:.1f}MB"),
        t2.row(f"bitmm_arith_intensity={flops / bytes_:.1f}flop/B"),
    ]


def bench_codec_sweep(B: int = 4096) -> list[str]:
    """Backend × batch × (n, k): batched encode vs the per-object loop.

    The acceptance bar for the unified engine: batched throughput ≥ the
    per-object loop at batch ≥ 8 on the jnp or pallas-interpret backend
    (per-launch/trace overhead amortized across the admission round).
    """
    rng = np.random.default_rng(7)
    rows: list[str] = []
    for backend in ("numpy", "jnp", "pallas"):
        codec = Codec(backend)
        for n, k in ((8, 4), (12, 6)):
            for batch in (1, 8, 32):
                data = rng.integers(0, 256, size=(batch, k, B), dtype=np.uint8)
                # warm both paths (jit compile outside the timed region)
                codec.encode(data, n, k)
                codec.encode(data[0], n, k)
                mb = batch * k * B / 2**20

                t0 = time.monotonic()
                codec.encode(data, n, k)
                dt_batched = time.monotonic() - t0

                t0 = time.monotonic()
                for i in range(batch):
                    codec.encode(data[i], n, k)
                dt_looped = time.monotonic() - t0

                speedup = dt_looped / max(dt_batched, 1e-9)
                timer = BenchTimer(f"codec_encode_{backend}_n{n}k{k}_b{batch}", calls=1)
                timer.elapsed = dt_batched
                rows.append(
                    timer.row(
                        f"batched={mb / dt_batched:.1f}MB/s"
                        f"|looped={mb / dt_looped:.1f}MB/s"
                        f"|speedup={speedup:.2f}x"
                    )
                )
    return rows


def bench_fleet_sweep(count: int = 1024, grids: tuple = (8, 64, 256)) -> list[str]:
    """Vmapped fleet sweep vs the serial host loop at grid sizes {8, 64, 256}.

    The serial baseline dispatches one jitted ``simulate_tofec_scan`` per
    grid point (the pre-fleet λ-sweep shape); the fleet runs the same grid
    as chunked vmapped launches. At grid 8 the discrete-event simulator is
    also timed for scale (the original Fig.1/7 inner loop — why the fleet
    subsystem exists).
    """
    from repro.core.controller import TofecTables
    from repro.core.jax_sim import JaxSimParams, simulate_tofec_scan
    from repro.core.simulator import poisson_arrivals, simulate
    from repro.core.static_optimizer import build_class_plan
    from repro.core.traces import TraceSampler
    from repro.fleet import FleetSweep, PolicySpec, grid_cases

    cls = RequestClass("read3mb", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12)
    L = 16
    tables = TofecTables.from_plan(build_class_plan(cls, L))
    p = JaxSimParams.from_class(cls, L)
    sampler = TraceSampler(PAPER_READ_3MB, cls.file_mb)
    sweep = FleetSweep(chunk=64)
    rows: list[str] = []
    for grid in grids:
        lams = np.linspace(5.0, 65.0, max(grid // 8, 1))
        seeds = range(-(-grid // len(lams)))  # pad seeds so len(cases) >= grid
        cases = grid_cases(lams, [PolicySpec.tofec()], seeds, cls, L)[:grid]

        sweep.run(cases, count)  # warm the shape bucket (compile + workloads)
        t0 = time.monotonic()
        res = sweep.run(cases, count)
        jax.block_until_ready(res.out)  # async dispatch: sync before stopping
        dt_fleet = time.monotonic() - t0

        # Serial host loop: one jitted scan dispatch per point, same draws.
        simulate_tofec_scan(p, tables, *map(jnp.asarray, _point_arrays(cases[0], count)))
        t0 = time.monotonic()
        for case in cases:
            inter, exps = _point_arrays(case, count)
            simulate_tofec_scan(p, tables, jnp.asarray(inter), jnp.asarray(exps))[
                "total"
            ].block_until_ready()
        dt_serial = time.monotonic() - t0

        derived = (f"serial_scan={1e3 * dt_serial:.1f}ms"
                   f"|speedup={dt_serial / max(dt_fleet, 1e-9):.2f}x"
                   f"|launches={res.launches}|compiles={res.compiles}")
        if grid <= 8:
            t0 = time.monotonic()
            for case in cases:
                rng = np.random.default_rng(case.seed)
                arr = poisson_arrivals(rng, case.lam, count)
                simulate(TOFECPolicy.for_classes([cls], L), arr, sampler, L=L,
                         seed=case.seed)
            dt_event = time.monotonic() - t0
            derived += (f"|event_sim={1e3 * dt_event:.1f}ms"
                        f"|vs_event={dt_event / max(dt_fleet, 1e-9):.1f}x")
        timer = BenchTimer(f"fleet_sweep_g{grid}_t{count}", calls=1)
        timer.elapsed = dt_fleet
        rows.append(timer.row(derived))
    return rows


def _point_arrays(case, count: int):
    rng = np.random.default_rng(case.seed)
    return case.resolved_workload().device_arrays(rng, count, case.cls.n_max)


def bench_multiclass_sweep(count: int = 1024, grids: tuple = (6, 24, 96)) -> list[str]:
    """Joint shared-pool sweep vs per-class split scans vs the event oracle.

    The joint path (:class:`repro.sched.SchedSweep`) runs each grid point as
    ONE multi-class scan over the merged stream; the split baseline runs the
    same grids through the fleet's Poisson-splitting ``tenant_cases`` path
    (2 fluid scans per point — cheaper per point but blind to interference);
    at the smallest grid the discrete-event shared-pool oracle
    (:func:`repro.core.simulator.simulate_shared_pool`) is timed for scale.
    """
    from repro.core import TOFECPolicy, build_class_plan
    from repro.core.simulator import simulate_shared_pool
    from repro.core.traces import TraceSampler
    from repro.fleet import FleetSweep, PolicySpec, TenantMix, tenant_cases
    from repro.sched import DisciplineSpec, SchedSweep, sched_cases

    hi = RequestClass("read3mb", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12)
    lo = RequestClass("read1mb", 1.0, PAPER_READ_3MB, k_max=4, r_max=2.0, n_max=8)
    L = 16
    disciplines = [DisciplineSpec.fifo(), DisciplineSpec.priority(0, 1),
                   DisciplineSpec.wfq(2.0, 1.0)]
    rows: list[str] = []
    for grid in grids:
        n_mix = max(grid // (len(disciplines) * 2), 1)
        mixes = [TenantMix(float(lam), (hi, lo), (0.5, 0.5))
                 for lam in np.linspace(10.0, 55.0, n_mix)]
        seeds = range(-(-grid // (n_mix * len(disciplines))))
        cases = sched_cases(mixes, disciplines, seeds, L=L)[:grid]

        joint = SchedSweep(chunk=32)
        joint.run(cases, count)  # warm the shape bucket
        t0 = time.monotonic()
        res = joint.run(cases, count)
        jax.block_until_ready(res.out)
        dt_joint = time.monotonic() - t0

        split_cases = [
            c for case in cases
            for c in tenant_cases(case.mix, [PolicySpec.tofec()], [case.seed], L,
                                  quiet=True)
        ]
        fleet = FleetSweep(chunk=64)
        fleet.run(split_cases, count)  # warm
        t0 = time.monotonic()
        sres = fleet.run(split_cases, count)
        jax.block_until_ready(sres.out)
        dt_split = time.monotonic() - t0

        derived = (f"split_fleet={1e3 * dt_split:.1f}ms"
                   f"|joint_vs_split={dt_split / max(dt_joint, 1e-9):.2f}x"
                   f"|launches={res.launches}|compiles={res.compiles}")
        if grid <= 8:
            pols = [TOFECPolicy([build_class_plan(c, L)]) for c in (hi, lo)]
            samp = [TraceSampler(c.params, c.file_mb) for c in (hi, lo)]
            t0 = time.monotonic()
            for case in cases:
                rng = np.random.default_rng(case.seed)
                arr = np.cumsum(case.mix.interarrivals(rng, count).astype(np.float64))
                ids = case.mix.cls_ids(rng, count)
                kw = {}
                if case.discipline.kind == "priority":
                    kw["prio"] = case.discipline.prio
                if case.discipline.kind == "wfq":
                    kw["weights"] = case.discipline.weights
                simulate_shared_pool(pols, arr, ids, samp, L=L,
                                     discipline=case.discipline.kind, **kw)
            dt_event = time.monotonic() - t0
            derived += (f"|event_sim={1e3 * dt_event:.1f}ms"
                        f"|vs_event={dt_event / max(dt_joint, 1e-9):.1f}x")
        timer = BenchTimer(f"multiclass_sweep_g{grid}_t{count}", calls=1)
        timer.elapsed = dt_joint
        rows.append(timer.row(derived))
    return rows


def bench_taskq_engine(count: int = 1024, grids: tuple = (8, 64)) -> list[str]:
    """Exact task-level engine: vmapped sweep vs serial scan vs event oracle.

    The vmapped path runs the whole grid through :class:`repro.taskq.
    TaskqSweep` (chunked launches, pools broadcast); the serial baseline
    dispatches one jitted :func:`repro.taskq.engine.taskq_scan` per point on
    the same draws; at grid 8 the discrete-event oracle
    (:func:`repro.core.simulator.simulate`) is timed on the same shared
    pools — the loop the exact engine replaces.
    """
    from repro.core.traces import TraceStore
    from repro.core.simulator import simulate
    from repro.fleet import PolicySpec, grid_cases
    from repro.taskq import TaskqSweep, taskq_scan, taskq_streams

    cls = RequestClass("read3mb", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12)
    L = 16
    store = TraceStore.generate(
        PAPER_READ_3MB, [cls.file_mb / k for k in range(1, cls.k_max + 1)],
        threads=cls.n_max, samples=4096, correlation=0.14, seed=0,
    )
    dp = store.device_pools(n_max=cls.n_max)
    pools_j, sizes_j = jnp.asarray(dp.pools), jnp.asarray(dp.sizes_mb)
    sweep = TaskqSweep(chunk=64)
    rows: list[str] = []
    for grid in grids:
        lams = np.linspace(5.0, 60.0, max(grid // 8, 1))
        seeds = range(-(-grid // len(lams)))
        cases = grid_cases(lams, [PolicySpec.tofec()], seeds, cls, L)[:grid]

        sweep.run(cases, count, dp)  # warm the shape bucket
        t0 = time.monotonic()
        res = sweep.run(cases, count, dp)
        jax.block_until_ready(res.out)
        dt_vmap = time.monotonic() - t0

        # Serial baseline: one jitted single-point scan per grid point.
        def one(case):
            inter, idx = taskq_streams(case, count, dp.n_rows)
            cfg = {name: jnp.asarray(res.cfg[name][cases.index(case)])
                   for name in res.cfg}
            return taskq_scan(cfg, jnp.asarray(inter), jnp.asarray(idx),
                              pools_j, sizes_j, L=L, q_cap=sweep.q_cap)

        one(cases[0])["total"].block_until_ready()  # warm
        t0 = time.monotonic()
        for case in cases:
            one(case)["total"].block_until_ready()
        dt_serial = time.monotonic() - t0

        derived = (f"serial_scan={1e3 * dt_serial:.1f}ms"
                   f"|speedup={dt_serial / max(dt_vmap, 1e-9):.2f}x"
                   f"|launches={res.launches}|compiles={res.compiles}")
        if grid <= 8:
            from repro.core import TOFECPolicy, build_class_plan

            t0 = time.monotonic()
            for case in cases:
                inter, idx = taskq_streams(case, count, dp.n_rows)
                arr = np.cumsum(inter.astype(np.float64))
                simulate(TOFECPolicy([build_class_plan(cls, L)]), arr,
                         dp.host_sampler(cls.file_mb, idx), L=L)
            dt_event = time.monotonic() - t0
            derived += (f"|event_sim={1e3 * dt_event:.1f}ms"
                        f"|vs_event={dt_event / max(dt_vmap, 1e-9):.1f}x")
        timer = BenchTimer(f"taskq_engine_g{grid}_t{count}", calls=1)
        timer.elapsed = dt_vmap
        rows.append(timer.row(derived))
    return rows


def bench_shard_scaling(count: int = 1024, grid: int = 1024,
                        big_grid: int = 100_000, big_count: int = 512,
                        devices: tuple = (1, 2, 4, 8)) -> list[str]:
    """Mesh-sharded streaming fleet sweep: device scaling + memory bound.

    For each device count (host virtual devices when launched under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``; counts beyond
    the available devices are skipped): time the sharded **streamed** sweep
    on a mixed-policy grid and assert its frontier is a bit-exact equal of
    the single-device **materialized** baseline. Then a ``big_grid``-point
    streamed run demonstrates the O(chunk × devices) memory bound — no
    (G, T) block ever materializes. Writes ``BENCH_shard.json``.

    Speedup is physical: with fewer host cores than virtual devices (CI
    runners), sharding only adds collective overhead — the artifact records
    ``host_cores`` so readers can tell scaling rows from placebo rows, and
    the >1.8x @ 4-device bar is only asserted when 4 real cores exist.
    """
    import json as _json
    import os as _os

    from repro.fleet import FleetSweep, PolicySpec, frontier_points, grid_cases
    from benchmarks.common import RESULTS_DIR

    cls = RequestClass("read3mb", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12)
    L = 16
    pols = [PolicySpec.tofec(), PolicySpec.static(6, 3), PolicySpec.fixedk(4)]

    def mixed_grid(g: int) -> list:
        lams = np.linspace(5.0, 65.0, max(-(-g // (len(pols) * 4)), 1))
        return grid_cases(lams, pols, range(4), cls, L)[:g]

    cases = mixed_grid(grid)
    n_dev = len(jax.devices())
    rows: list[str] = []

    # Single-device materialized baseline: the pre-shard path, timed AND the
    # bit-exactness reference for every sharded-streaming run.
    base = FleetSweep(chunk=128)
    base.run(cases[: min(256, grid)], count)  # warm the shape bucket
    t0 = time.monotonic()
    ref = base.run(cases, count)
    jax.block_until_ready(ref.out)
    dt_base = time.monotonic() - t0
    ref_pts = [p.to_dict() for p in frontier_points(ref)]
    timer = BenchTimer(f"shard_baseline_g{grid}_t{count}", calls=1)
    timer.elapsed = dt_base
    rows.append(timer.row(f"materialized|devices=1|launches={ref.launches}"))

    scaling, dt_one = [], None
    for d in devices:
        if d > n_dev:
            continue
        sweep = FleetSweep(chunk=128, mesh=d)
        sweep.run(cases[: min(256, grid)], count, stream=True)
        t0 = time.monotonic()
        res = sweep.run(cases, count, stream=True)
        dt = time.monotonic() - t0
        assert res.out == {}  # streamed: no (G, T) block
        pts = [p.to_dict() for p in frontier_points(res)]
        assert _json.dumps(pts) == _json.dumps(ref_pts), \
            f"sharded-streaming frontier diverged at d={d}"
        dt_one = dt if d == 1 else dt_one
        speedup = (dt_one or dt) / max(dt, 1e-9)
        scaling.append({"devices": d, "ms": 1e3 * dt, "speedup_vs_1dev": speedup,
                        "bit_exact": True})
        timer = BenchTimer(f"shard_stream_d{d}_g{grid}_t{count}", calls=1)
        timer.elapsed = dt
        rows.append(timer.row(f"speedup={speedup:.2f}x|bit_exact=True"
                              f"|launches={res.launches}"))

    cores = _os.cpu_count() or 1
    if cores >= 4 and n_dev >= 4 and grid >= 1024:
        at4 = next(s["speedup_vs_1dev"] for s in scaling if s["devices"] == 4)
        assert at4 > 1.8, f"4-device speedup {at4:.2f}x <= 1.8x with {cores} cores"

    # Streamed-memory bound: a big grid whose materialized block would be
    # G × T × 20 B never exists — peak device residency is chunk-sized.
    big = mixed_grid(big_grid)
    d_big = max(d for d in devices if d <= n_dev)
    sweep = FleetSweep(chunk=128, mesh=None if d_big == 1 else d_big)
    sweep.run(big[: min(256, big_grid)], big_count, stream=True)  # warm
    t0 = time.monotonic()
    res = sweep.run(big, big_count, stream=True)
    dt_big = time.monotonic() - t0
    assert res.out == {} and len(frontier_points(res)) == big_grid
    mat_mb = big_grid * big_count * 20 / 2**20  # 3×f32 + 2×i32 per request
    str_mb = (128 * d_big * big_count * 20 + big_grid * 15 * 4) / 2**20
    timer = BenchTimer(f"shard_stream_big_g{big_grid}_t{big_count}", calls=1)
    timer.elapsed = dt_big
    rows.append(timer.row(
        f"devices={d_big}|req_per_s={big_grid * big_count / dt_big:.0f}"
        f"|materialized_would_be={mat_mb:.0f}MB"
        f"|streamed_peak~{str_mb:.0f}MB"))

    _os.makedirs(RESULTS_DIR, exist_ok=True)
    artifact = {
        "schema": "repro.fleet/BENCH_shard/v1",
        "meta": _obs.run_meta(mesh_shape=(d_big,)),
        "grid": grid, "count": count,
        "big_grid": big_grid, "big_count": big_count,
        "host_devices": n_dev, "host_cores": cores,
        "baseline_materialized_ms": 1e3 * dt_base,
        "scaling": scaling,
        "big_grid_ms": 1e3 * dt_big,
        "big_grid_devices": d_big,
        "materialized_would_be_mb": mat_mb,
        "streamed_peak_mb": str_mb,
    }
    with open(_os.path.join(RESULTS_DIR, "BENCH_shard.json"), "w") as f:
        _json.dump(artifact, f, indent=1)
    return rows


def bench_ckpt_encode(leaf_mb: int = 1) -> list[str]:
    rng = np.random.default_rng(1)
    payload = rng.integers(0, 256, size=leaf_mb * 2**20, dtype=np.uint8)
    with BenchTimer("ckpt_encode_blob", calls=1) as t:
        strips = ops.encode_blob(payload, n=8, k=4)
    present = (1, 3, 5, 7)
    with BenchTimer("ckpt_decode_blob", calls=1) as t2:
        out = ops.decode_blob(strips[list(present)], present, n=8, k=4,
                              payload_len=payload.size)
    assert np.array_equal(out, payload)
    mbps = leaf_mb / t.elapsed
    return [t.row(f"encode_{leaf_mb}MB@{mbps:.1f}MB/s"), t2.row("decode_ok")]


ALL_KERNEL = [
    bench_gf2mm,
    bench_codec_sweep,
    bench_fleet_sweep,
    bench_multiclass_sweep,
    bench_taskq_engine,
    bench_shard_scaling,
    bench_ckpt_encode,
]
