"""Vmapped fleet sweep: a whole (λ × policy × seed) grid per jitted launch.

One grid point = one :func:`repro.core.jax_sim.tofec_scan_core` run. The
sweep stacks every per-point quantity — delay-model params, threshold
tables, redundancy cap, arrival/exponential draws — along a leading grid
axis and ``vmap``s the scan core over it, so a 256-point λ-sweep costs a
handful of launches instead of 256 serial ones.

Uniformity across the grid is manufactured, not assumed:

* **Policies as tables.** The scan's controller is the threshold form
  ``1 + #{h > q̄}``; :func:`repro.core.controller.policy_tables` encodes
  static (n, k) codes and the fixed-k adaptive strategy of [3] into the
  same (h_k, h_n, r_max) triple as TOFEC, so heterogeneous policy mixes
  ride one vmapped launch.
* **Shape-bucketed jit caching.** Following the ``Codec.pad_to_bucket``
  convention, compiled sweeps are keyed on (chunk, pow2-bucketed T, n_max,
  table lengths); trailing-zero threshold padding and zero-gap arrival
  padding are semantically inert (outputs are sliced back), so
  heterogeneous grids compile once per bucket — asserted in
  ``tests/test_fleet.py``.
* **Memory-bounded chunked batching.** The grid axis is split into
  ``chunk``-sized launches (the last chunk padded by repetition), bounding
  per-launch device footprint at chunk × T × (n_max + 2) float32s.
"""

from __future__ import annotations

import dataclasses
import types

import numpy as np

from repro import obs
from repro.coding.codec import pow2_bucket
from repro.core.controller import PolicySpec, policy_tables
from repro.core.delay_model import RequestClass
from repro.core.static_optimizer import ClassPlan, build_class_plan
from repro.fleet.workloads import PoissonWorkload, TenantMix, Workload


# ---------------------------------------------------------------------------
# Grid construction
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SweepCase:
    """One grid point: arrival process × policy × seed (× class, L)."""

    lam: float
    policy: PolicySpec
    seed: int
    cls: RequestClass
    L: int = 16
    workload: Workload | None = None  # default: Poisson(lam)

    def resolved_workload(self) -> Workload:
        return self.workload if self.workload is not None else PoissonWorkload(self.lam)


def grid_cases(
    lams,
    policies,
    seeds,
    cls: RequestClass,
    L: int = 16,
    workload_for=None,
) -> list[SweepCase]:
    """Cartesian λ × policy × seed grid; ``workload_for(lam)`` optionally
    maps each rate to a non-Poisson workload spec."""
    return [
        SweepCase(
            lam=float(lam), policy=pol, seed=int(seed), cls=cls, L=L,
            workload=workload_for(float(lam)) if workload_for else None,
        )
        for lam in lams
        for pol in policies
        for seed in seeds
    ]


def tenant_cases(
    mix: TenantMix, policies, seeds, L: int = 16, *, quiet: bool = False
) -> list[SweepCase]:
    """Expand a multi-tenant mix into per-class grid points (Poisson
    splitting): each class rides the sweep with its own tables and its
    split rate w·λ.

    .. note:: This is the documented **approximation path**: splitting gives
       every class an independent fluid queue that believes it owns all L
       threads, so cross-class interference — §IV's shared-resource story —
       is invisible (a starved low-priority class, FIFO head-of-line
       coupling, weighted shares). Use :class:`repro.sched.SchedSweep` with
       a :class:`repro.sched.DisciplineSpec` for the joint shared-pool
       simulation; pass ``quiet=True`` here when the fluid split is wanted
       deliberately (e.g. as the no-interference baseline in benchmarks).
    """
    if not quiet:
        import warnings

        warnings.warn(
            "tenant_cases() Poisson-splits the mix into independent per-class "
            "fluid queues and cannot show cross-class interference; use "
            "repro.sched (SchedSweep + DisciplineSpec) for the joint "
            "shared-pool simulation, or pass quiet=True to keep the fluid "
            "split deliberately.",
            UserWarning,
            stacklevel=2,
        )
    return [
        SweepCase(lam=sub.lam, policy=pol, seed=int(seed), cls=c, L=L, workload=sub)
        for c, sub in mix.split()
        for pol in policies
        for seed in seeds
    ]


# ---------------------------------------------------------------------------
# The vmapped sweep engine
# ---------------------------------------------------------------------------


#: Back-compat alias — the per-engine counter dataclass now lives in
#: :mod:`repro.obs` so retrace accounting is uniform across engines.
SweepStats = obs.CompileStats


class ChunkedVmapSweep:
    """Shared engine for chunked, shape-bucketed vmapped case sweeps.

    Owns what :class:`FleetSweep` and :class:`repro.sched.sweep.SchedSweep`
    have in common: the compile cache keyed by shape bucket, the
    trace-counting jit+vmap wrapper, the per-(class, L) plan cache, and the
    chunked launch loop (tail chunk padded by repetition, outputs sliced
    back and restacked). Subclasses define the bucket key, the per-case
    config stacking and the single-case scan body.

    ``chunk`` bounds the grid points per launch (memory bound); ``t_floor``
    floors the pow2 time-axis bucket so nearby horizon lengths share a
    compilation, mirroring ``Codec.B_FLOOR``.

    ``mesh`` (None | int device count | 1-D jax Mesh) shards every launch's
    chunk axis across a device mesh via :func:`repro.fleet.shard.
    shard_grid`: axis-0 operands split along the grid axis, ``in_axes=None``
    broadcast operands replicate. Compilations are keyed additionally on
    the mesh shape, and the effective chunk is rounded up to a mesh-size
    multiple so every device owns an equal slice.
    """

    T_FLOOR = 512

    def __init__(self, *, chunk: int = 64, t_floor: int | None = None,
                 mesh=None):
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        from repro.fleet.shard import resolve_grid_mesh

        self.chunk = chunk
        self.t_floor = t_floor or self.T_FLOOR
        self.mesh = resolve_grid_mesh(mesh)
        self.stats = obs.CompileStats(label=f"sweep.{type(self).__name__}")
        self._fns: dict[tuple, object] = {}
        self._plans: dict[tuple, ClassPlan] = {}
        self._last_metrics = None  # MetricsBuf of the most recent run, if collected
        self._last_timeline = None  # per-case TimelineBuf of the most recent run

    @property
    def mesh_shape(self) -> tuple:
        """Device-mesh shape key: () single-device, (D,) for a grid mesh."""
        return () if self.mesh is None else tuple(self.mesh.devices.shape)

    @property
    def mesh_size(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.devices.size)

    def _chunk_bucket(self, n_cases: int) -> int:
        """Effective per-launch chunk: pow2-bucketed grid size capped at
        ``chunk``, then rounded up to a mesh-size multiple so ``shard_map``
        can split the chunk axis evenly across devices."""
        c = min(pow2_bucket(n_cases), self.chunk)
        d = self.mesh_size
        return -(-c // d) * d

    def _vmapped(self, one, in_axes: tuple):
        """jit(vmap(one, in_axes)) with a trace-time counter feeding
        ``stats``. ``in_axes`` entries of ``None`` mark grid-shared broadcast
        arguments (e.g. the taskq engine's trace pools) that every grid row
        reads without a per-row copy; on a mesh they are the replicated
        operands while axis-0 entries shard along the grid axis.

        Per-chunk operands (the axis-0 args) are donated: each chunk uploads
        fresh config/stream buffers that nothing re-reads after the launch,
        so XLA may reuse their device memory for the outputs. Broadcast
        operands live across launches and are never donated.
        """
        import jax

        def fn(*args):
            self.stats.traces += 1  # runs at trace time only
            key = self.mesh_shape
            self.stats.by_mesh[key] = self.stats.by_mesh.get(key, 0) + 1
            with obs.span("sweep.trace", engine=type(self).__name__,
                          mesh=str(key)):
                return jax.vmap(one, in_axes=in_axes)(*args)

        donate = tuple(i for i, ax in enumerate(in_axes) if ax == 0)
        if self.mesh is not None:
            from repro.fleet.shard import shard_grid

            fn = shard_grid(fn, self.mesh, in_axes)
        return jax.jit(fn, donate_argnums=donate)

    def _build(self, key: tuple, collect: bool = False):
        raise NotImplementedError

    def _fn_for(self, key: tuple, collect: bool = False):
        """``collect`` (metrics on/off) is part of the cache key: a constant
        ``REPRO_OBS`` setting yields exactly the pinned compile counts, and
        flipping it mid-process recompiles instead of mis-tracing."""
        fn = self._fns.get((key, collect))
        if fn is None:
            fn = self._fns[(key, collect)] = self._build(key, collect)
        return fn

    def _plan_for(self, cls: RequestClass, L: int, eq7_factor: float) -> ClassPlan:
        key = (cls, L, eq7_factor)
        plan = self._plans.get(key)
        if plan is None:
            plan = self._plans[key] = build_class_plan(cls, L, eq7_factor=eq7_factor)
        return plan

    def _launch_chunks(self, fn, cfg, streams, G: int, chunk: int, count: int,
                       broadcast: tuple = (), fold=None):
        """ceil(G / chunk) launches over (cfg, *streams, *broadcast); returns
        the stacked (G, count) output dict. Tail-chunk rows are repetitions
        of row ``lo`` and sliced off before stacking, so padding never leaks.
        ``broadcast`` arguments are passed whole to every launch (no grid
        axis) — they must line up with ``None`` entries of the builder's
        ``in_axes``.

        ``streams`` is a callable ``(idx) -> tuple of (chunk, ...) blocks``
        generating one chunk's host-side streams on demand from the padded
        case-index array — host memory never holds more than one chunk of
        workload draws, which is what lets a 1e5-point grid run at all.
        (A tuple of full (G, ...) arrays is still accepted and gathered
        per chunk.)

        The chunk gather rides one preallocated index buffer (no per-chunk
        concatenate), and the per-chunk device uploads are donated to the
        launch (see :meth:`_vmapped`), so peak memory stays at one chunk's
        working set on both host and device.

        ``fold`` streams: called per launch as ``fold(out, cfg_np, streams_np)``
        with the chunk's outputs sliced to ``[:, :count]`` and the chunk's
        host-side config/stream rows, it returns fixed-size per-row
        statistics which are stacked *instead of* the raw (chunk, T) block —
        the block itself is dropped before the next launch, so a streamed
        sweep never materializes O(G × T).
        """
        import warnings

        import jax.numpy as jnp

        outs = []
        mbuf = None
        tlbuf = None
        engine = type(self).__name__
        mesh_tag = str(self.mesh_shape)
        bcast = tuple(jnp.asarray(b) for b in broadcast)
        idx = np.empty(chunk, np.intp)  # preallocated chunk-gather indices
        for lo in range(0, G, chunk):
            hi = min(lo + chunk, G)
            with obs.span("sweep.chunk", engine=engine, mesh=mesh_tag,
                          rows=hi - lo):
                idx[: hi - lo] = np.arange(lo, hi)
                idx[hi - lo:] = lo  # pad the tail chunk by repetition
                with obs.span("sweep.hostgen", engine=engine):
                    cfg_np = {name: v[idx] for name, v in cfg.items()}
                    streams_np = (
                        streams(idx) if callable(streams)
                        else tuple(s[idx] for s in streams)
                    )
                with warnings.catch_warnings():
                    # Donated operands with no same-sized output (e.g. the
                    # (chunk, T, n_max) Exp draws) cannot be aliased; XLA warns
                    # about that expected partial usability on every compile.
                    warnings.filterwarnings(
                        "ignore", message="Some donated buffers were not usable"
                    )
                    with obs.span("sweep.launch", engine=engine, mesh=mesh_tag):
                        out = fn(
                            {name: jnp.asarray(v) for name, v in cfg_np.items()},
                            *(jnp.asarray(s) for s in streams_np), *bcast)
                self.stats.launches += 1
                # The per-case metrics fold rides the same launch: slice off
                # the tail padding, row-reduce on device, merge across chunks
                # (mirrors the streamed frontier folds — no host syncs).
                out = dict(out)
                mb = out.pop("obs", None)
                if mb is not None:
                    mb = mb.reduce_rows(hi - lo)
                    mbuf = mb if mbuf is None else mbuf.merge(mb)
                # Timelines stay per case: cut the tail padding, then
                # concatenate chunks along the case axis (leading-batch
                # invariant, so streamed/sharded runs carry them bit-exactly).
                tl = out.pop("timeline", None)
                if tl is not None:
                    tl = tl.reduce_rows(hi - lo)
                    tlbuf = tl if tlbuf is None else tlbuf.concat(tl)
                if fold is None:
                    outs.append(
                        {name: v[: hi - lo, :count] for name, v in out.items()})
                else:
                    with obs.span("sweep.fold", engine=engine):
                        red = fold({name: v[:, :count] for name, v in out.items()},
                                   cfg_np, streams_np)
                    outs.append({name: v[: hi - lo] for name, v in red.items()})
        self.stats.cases += G
        self._last_metrics = mbuf
        self._last_timeline = tlbuf
        return {
            name: jnp.concatenate([o[name] for o in outs], axis=0)
            for name in outs[0]
        }


def frontier_fold(w: int, bins: int):
    """Per-chunk streaming fold for fleet-style (single-class) sweeps.

    Runs the SAME jitted reduction kernels the materialized frontier uses
    (:func:`repro.fleet.stats.frontier_block_reduce` for the delay/usage
    statistics, :func:`repro.fleet.stats.convergence_reduce` for the
    adaptation integers) on one (chunk, count) block at a time — per-row
    reductions are leading-batch invariant, so the streamed statistics are
    bit-exact equals of the materialized ones. ``w`` is the warmup cut,
    ``bins`` any bound exceeding every chosen k (table length works).
    """
    import jax.numpy as jnp

    from repro.fleet.stats import convergence_reduce, frontier_block_reduce

    def fold(out, cfg_np, streams_np):
        red = dict(frontier_block_reduce(
            out, jnp.asarray(cfg_np["delta_bar"]),
            jnp.asarray(cfg_np["delta_tilde"]), jnp.asarray(cfg_np["psi_bar"]),
            jnp.asarray(cfg_np["psi_tilde"]), jnp.asarray(cfg_np["J"]), w=w,
        ))
        red.update(convergence_reduce(out["k"], w=w, bins=bins))
        return red

    return fold


@dataclasses.dataclass
class SweepResult:
    """Stacked per-request outputs for every grid point.

    ``out`` holds device arrays of shape (G, count): ``total``/``queueing``/
    ``service`` delays (float32) and the chosen ``n``/``k`` (int32) — kept
    on device so :mod:`repro.fleet.frontier` reduces them without a host
    round-trip. ``cfg`` is the stacked per-case config (params + tables).

    A **streamed** run (``run(..., stream=...)``) never materializes the
    (G, count) block: ``out`` is empty and ``streamed`` carries the running
    frontier reduction (:class:`repro.fleet.shard.StreamedStats`) that the
    frontier consumers read instead.
    """

    cases: list[SweepCase]
    out: dict
    cfg: dict[str, np.ndarray]
    count: int
    compiles: int
    launches: int
    streamed: object = None  # StreamedStats for streamed runs
    metrics: object = None  # MetricsBuf folded across chunks (REPRO_OBS=1)
    timeline: object = None  # per-case TimelineBuf, (G, S) slots (REPRO_OBS=1)
    mesh_shape: tuple = ()  # device-mesh shape the run launched on

    def to_numpy(self) -> dict[str, np.ndarray]:
        return {k: np.asarray(v) for k, v in self.out.items()}


class FleetSweep(ChunkedVmapSweep):
    """Chunked, shape-bucketed vmapped sweep over :class:`SweepCase` grids."""

    # -- compilation cache --------------------------------------------------

    def bucket_key(self, n_cases: int, count: int, n_max: int, hk_len: int, hn_len: int):
        """The compilation-cache key a run with these shapes lands in.

        The trailing timeline window is derived from the pow2 time bucket
        (see :func:`repro.obs.timeline_window`), so listing it explicitly
        never splits a bucket — it documents the slotting each compilation
        traces with."""
        t_b = pow2_bucket(count, self.t_floor)
        return (
            self._chunk_bucket(n_cases),
            t_b,
            n_max,
            hk_len,
            hn_len,
            self.mesh_shape,
            obs.timeline_window(t_b),
        )

    def _build(self, key: tuple, collect: bool = False):
        n_max = key[2]
        window = key[-1]

        def one(cfg, inter, exps):
            from repro.core.jax_sim import backlog_proxy, tofec_scan_core

            p = types.SimpleNamespace(
                delta_bar=cfg["delta_bar"], delta_tilde=cfg["delta_tilde"],
                psi_bar=cfg["psi_bar"], psi_tilde=cfg["psi_tilde"],
                J=cfg["J"], L=cfg["L"], alpha=cfg["alpha"],
            )
            out = tofec_scan_core(
                p, cfg["h_k"], cfg["h_n"], cfg["r_max"], inter, exps, n_max=n_max
            )
            if collect:
                out = dict(out)
                valid = obs.valid_mask(cfg, inter.shape[-1])
                out["obs"] = obs.sweep_point_metrics(out, "fleet", valid=valid)
                out["timeline"] = obs.sweep_timeline(
                    out, inter, window=window, valid=valid,
                    backlog=backlog_proxy(p, out["queueing"]))
            return out

        return self._vmapped(one, in_axes=(0, 0, 0))

    # -- the sweep ----------------------------------------------------------

    def _stack_cfg(self, cases: list[SweepCase], hk_len: int, hn_len: int):
        G = len(cases)
        cfg = {
            name: np.empty(G, np.float32)
            for name in ("delta_bar", "delta_tilde", "psi_bar", "psi_tilde",
                         "J", "L", "alpha", "r_max")
        }
        cfg["h_k"] = np.zeros((G, hk_len), np.float32)
        cfg["h_n"] = np.zeros((G, hn_len), np.float32)
        for i, case in enumerate(cases):
            plan = (
                self._plan_for(case.cls, case.L, case.policy.eq7_factor)
                if case.policy.kind == "tofec" else None
            )
            h_k, h_n, r_max = policy_tables(case.policy, case.cls, case.L, plan)
            pr = case.cls.params
            cfg["delta_bar"][i] = pr.delta_bar
            cfg["delta_tilde"][i] = pr.delta_tilde
            cfg["psi_bar"][i] = pr.psi_bar
            cfg["psi_tilde"][i] = pr.psi_tilde
            cfg["J"][i] = case.cls.file_mb
            cfg["L"][i] = case.L
            cfg["alpha"][i] = case.policy.alpha
            cfg["r_max"][i] = r_max
            # Trailing zeros are inert thresholds (0 > q̄ never holds), so
            # shorter per-class tables pad into the shared bucket for free.
            cfg["h_k"][i, : len(h_k)] = h_k
            cfg["h_n"][i, : len(h_n)] = h_n
        return cfg

    def run(self, cases: list[SweepCase], count: int, *,
            stream=None) -> SweepResult:
        """Evaluate every grid point over ``count`` arrivals.

        Host side: per-case RNG streams generate the workload arrays.
        Device side: ceil(G / chunk) vmapped launches, each hitting the
        shape-bucket cache.

        ``stream`` (True or a :class:`repro.fleet.shard.StreamSpec`) folds
        each chunk into running frontier statistics instead of stacking the
        raw (G, count) block — see :mod:`repro.fleet.shard`.
        """
        if not cases:
            raise ValueError("empty case grid")
        from repro.fleet.shard import StreamedStats, resolve_stream

        spec = resolve_stream(stream)
        traces0, launches0 = self.stats.traces, self.stats.launches
        n_max = max(c.cls.n_max for c in cases)
        hk_len = max(c.cls.k_max for c in cases) + 1
        hn_len = n_max + 1
        key = self.bucket_key(len(cases), count, n_max, hk_len, hn_len)
        chunk, T_b = key[0], key[1]

        cfg = self._stack_cfg(cases, hk_len, hn_len)
        G = len(cases)
        collect = obs.enabled()
        if collect:
            # Runtime row, not a cache-key entry: runs sharing a pow2 time
            # bucket keep sharing one compilation.
            cfg["obs_count"] = np.full(G, count, np.int32)

        def chunk_streams(idx):
            inter = np.zeros((len(idx), T_b), np.float32)
            exps = np.zeros((len(idx), T_b, n_max), np.float32)
            for j, i in enumerate(idx):
                if j and i == idx[0]:  # tail pad: repeat the chunk's row 0
                    inter[j], exps[j] = inter[0], exps[0]
                    continue
                case = cases[i]
                rng = np.random.default_rng(case.seed)
                it, ex = case.resolved_workload().device_arrays(
                    rng, count, case.cls.n_max)
                inter[j, :count] = it
                # Classes with smaller n_max leave trailing Exp columns at
                # zero; the scan masks draws at j >= k, so padding never
                # enters.
                exps[j, :count, : case.cls.n_max] = ex
            return inter, exps

        fn = self._fn_for(key, collect)
        fold = (
            frontier_fold(int(count * spec.warmup_frac), hn_len)
            if spec else None
        )
        stacked = self._launch_chunks(fn, cfg, chunk_streams, G, chunk, count,
                                      fold=fold)
        return SweepResult(
            cases=list(cases),
            out={} if spec else stacked,
            cfg=cfg,
            count=count,
            compiles=self.stats.traces - traces0,
            launches=self.stats.launches - launches0,
            streamed=(
                StreamedStats(spec.warmup_frac, count, stacked) if spec else None
            ),
            metrics=self._last_metrics,
            timeline=self._last_timeline,
            mesh_shape=self.mesh_shape,
        )
