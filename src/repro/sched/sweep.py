"""Vmapped shared-pool sweep: (mix × discipline × seed) grids, jointly.

Mirrors :class:`repro.fleet.sweep.FleetSweep` — pow2-bucketed jit caching,
chunked memory-bounded launches, policies as threshold tables — but each
grid point is a whole multi-class system: one merged arrival stream, one
L-thread pool, per-class TOFEC state, and a per-point admission discipline
(:mod:`repro.sched.scan`). Disciplines travel as runtime data (id + rank +
weight arrays), so a grid mixing FIFO, strict priority and weighted-fair
points compiles ONCE per shape bucket — asserted in ``tests/test_sched.py``.

Shared-bucket rule: within one :meth:`SchedSweep.run`, every case is padded
to the run's widest class count C (dummy classes get zero tables, zero
weight and the lowest priority; their ids never occur in ``cls_ids``, so
they are semantically inert), and the compilation key is (chunk, pow2(T),
C, n_max, table lengths) — the fleet's ``Codec.pad_to_bucket`` convention
with a class axis.
"""

from __future__ import annotations

import dataclasses
import types

import numpy as np

from repro import obs
from repro.coding.codec import pow2_bucket
from repro.core.controller import PolicySpec, policy_tables
from repro.fleet.sweep import ChunkedVmapSweep
from repro.fleet.workloads import TenantMix
from repro.sched.scan import DISC_FIFO, DISC_PRIORITY, DISC_WFQ


@dataclasses.dataclass(frozen=True)
class DisciplineSpec:
    """Declarative admission discipline for one grid point.

    ``prio`` (priority only): per-class ranks, a permutation of range(C),
    lower = served first. ``weights`` (wfq only): positive per-class shares.
    """

    kind: str
    prio: tuple = ()
    weights: tuple = ()

    @classmethod
    def fifo(cls) -> "DisciplineSpec":
        return cls("fifo")

    @classmethod
    def priority(cls, *prio: int) -> "DisciplineSpec":
        return cls("priority", prio=tuple(int(r) for r in prio))

    @classmethod
    def wfq(cls, *weights: float) -> "DisciplineSpec":
        return cls("wfq", weights=tuple(float(w) for w in weights))

    @property
    def name(self) -> str:
        if self.kind == "priority":
            return f"priority({','.join(map(str, self.prio))})"
        if self.kind == "wfq":
            return f"wfq({':'.join(f'{w:g}' for w in self.weights)})"
        return "fifo"

    def validate(self, C: int) -> None:
        if self.kind == "priority":
            if sorted(self.prio) != list(range(C)):
                raise ValueError(f"priority ranks {self.prio} must permute range({C})")
        elif self.kind == "wfq":
            if len(self.weights) != C or any(w <= 0 for w in self.weights):
                raise ValueError(f"wfq weights {self.weights} must be {C} positives")
        elif self.kind != "fifo":
            raise ValueError(f"unknown discipline kind {self.kind!r}")

    def encode(self, C: int, C_pad: int):
        """(disc_id, prio (C_pad,), weights (C_pad,)) runtime arrays.

        Padded classes rank below every real one and carry zero weight —
        they never arrive, never backlog, never receive pool share.
        """
        self.validate(C)
        disc = {"fifo": DISC_FIFO, "priority": DISC_PRIORITY, "wfq": DISC_WFQ}[self.kind]
        prio = np.arange(C_pad, dtype=np.float32)
        if self.kind == "priority":
            prio[:C] = np.asarray(self.prio, np.float32)
            prio[C:] = C + np.arange(C_pad - C)
        weights = np.zeros(C_pad, np.float32)
        weights[:C] = np.asarray(self.weights, np.float32) if self.kind == "wfq" else 1.0
        return disc, prio, weights


@dataclasses.dataclass(frozen=True)
class SchedCase:
    """One grid point: a tenant mix × discipline × per-class policies × seed."""

    mix: TenantMix
    discipline: DisciplineSpec
    policy: object = None  # PolicySpec (shared) | tuple[PolicySpec, ...] | None→tofec
    seed: int = 0
    L: int = 16

    @property
    def lam(self) -> float:
        return self.mix.lam

    def policies(self) -> tuple[PolicySpec, ...]:
        C = len(self.mix.classes)
        pol = self.policy if self.policy is not None else PolicySpec.tofec()
        if isinstance(pol, PolicySpec):
            return (pol,) * C
        pol = tuple(pol)
        if len(pol) != C:
            raise ValueError(f"need {C} per-class policies, got {len(pol)}")
        return pol


def sched_cases(mixes, disciplines, seeds, *, policy=None, L: int = 16) -> list[SchedCase]:
    """Cartesian mix × discipline × seed grid of :class:`SchedCase`."""
    return [
        SchedCase(mix=mix, discipline=disc, policy=policy, seed=int(seed), L=L)
        for mix in mixes
        for disc in disciplines
        for seed in seeds
    ]


def multiclass_fold(w: int, C: int, count: int):
    """Per-chunk streaming fold for joint multi-class sweeps.

    Runs the SAME jitted per-class reduction the materialized path uses
    (:func:`repro.sched.frontier._reduce_multiclass`) on one (chunk, count)
    block at a time, rebuilding the chunk's ``cls_ids`` from the host-side
    class-id stream (the second stream operand). Per-row reductions are
    leading-batch invariant, so the streamed per-class statistics are
    bit-exact equals of the materialized ones.
    """
    import jax.numpy as jnp

    from repro.sched.frontier import _reduce_multiclass

    def fold(out, cfg_np, streams_np):
        ids_c = streams_np[1][:, :count]  # (chunk, count) class-id rows
        return dict(_reduce_multiclass(
            {**out, "cls_ids": jnp.asarray(ids_c)}, C=C, w=w,
        ))

    return fold


@dataclasses.dataclass
class SchedResult:
    """Stacked per-request outputs for every joint grid point.

    ``out`` holds (G, count) device arrays (``total``/``queueing``/
    ``service`` float32, ``n``/``k`` int32) plus ``cls_ids`` (G, count)
    int32 — kept on device so :mod:`repro.sched.frontier` masks per-class
    reductions without a host round-trip. A **streamed** run leaves ``out``
    empty and carries the running per-class reduction in ``streamed``
    (:class:`repro.fleet.shard.StreamedStats`) instead.
    """

    cases: list[SchedCase]
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


class SchedSweep(ChunkedVmapSweep):
    """Chunked, shape-bucketed vmapped sweep over :class:`SchedCase` grids.

    Shares the compile cache, trace counting and chunked launch loop with
    :class:`repro.fleet.sweep.FleetSweep` via :class:`repro.fleet.sweep.
    ChunkedVmapSweep`; differs in the bucket key (a class axis C), the
    per-case config (per-class vectors + discipline encoding) and the scan
    body (the joint multi-class core).
    """

    # -- compilation cache --------------------------------------------------

    def bucket_key(self, n_cases: int, count: int, C: int, n_max: int,
                   hk_len: int, hn_len: int):
        """The compilation-cache key a run with these shapes lands in.

        The trailing timeline window derives from the pow2 time bucket
        (:func:`repro.obs.timeline_window`), so listing it never splits a
        bucket."""
        t_b = pow2_bucket(count, self.t_floor)
        return (
            self._chunk_bucket(n_cases),
            t_b,
            C,
            n_max,
            hk_len,
            hn_len,
            self.mesh_shape,
            obs.timeline_window(t_b),
        )

    def _build(self, key: tuple, collect: bool = False):
        n_max = key[3]
        window = key[-1]

        def one(cfg, inter, cls_ids, exps):
            from repro import obs
            from repro.sched.scan import multiclass_scan_core

            p = types.SimpleNamespace(
                delta_bar=cfg["delta_bar"], delta_tilde=cfg["delta_tilde"],
                psi_bar=cfg["psi_bar"], psi_tilde=cfg["psi_tilde"],
                J=cfg["J"], L=cfg["L"], alpha=cfg["alpha"], r_max=cfg["r_max"],
            )
            out = multiclass_scan_core(
                p, cfg["h_k"], cfg["h_n"], cfg["disc"], cfg["prio"], cfg["wfq_w"],
                inter, cls_ids, exps, n_max=n_max,
            )
            if collect:
                out = dict(out)
                valid = obs.valid_mask(cfg, inter.shape[-1])
                out["obs"] = obs.sweep_point_metrics(out, "sched", valid=valid)
                # The joint scan does not expose a single-queue backlog (the
                # pool is shared across classes), so the sched timeline
                # carries rate/pick/delay series only.
                out["timeline"] = obs.sweep_timeline(
                    out, inter, window=window, valid=valid)
            return out

        return self._vmapped(one, in_axes=(0, 0, 0, 0))

    # -- the sweep ----------------------------------------------------------

    def _stack_cfg(self, cases: list[SchedCase], C: int, hk_len: int, hn_len: int):
        G = len(cases)
        cfg = {
            name: np.zeros((G, C), np.float32)
            for name in ("delta_bar", "delta_tilde", "psi_bar", "psi_tilde",
                         "J", "alpha", "r_max")
        }
        cfg["L"] = np.empty(G, np.float32)
        cfg["disc"] = np.empty(G, np.int32)
        cfg["prio"] = np.zeros((G, C), np.float32)
        cfg["wfq_w"] = np.zeros((G, C), np.float32)
        cfg["h_k"] = np.zeros((G, C, hk_len), np.float32)
        cfg["h_n"] = np.zeros((G, C, hn_len), np.float32)
        for i, case in enumerate(cases):
            disc, prio, wfq_w = case.discipline.encode(len(case.mix.classes), C)
            cfg["L"][i] = case.L
            cfg["disc"][i] = disc
            cfg["prio"][i] = prio
            cfg["wfq_w"][i] = wfq_w
            for c, (cls, spec) in enumerate(zip(case.mix.classes, case.policies())):
                plan = (
                    self._plan_for(cls, case.L, spec.eq7_factor)
                    if spec.kind == "tofec" else None
                )
                h_k, h_n, r_max = policy_tables(spec, cls, case.L, plan)
                pr = cls.params
                cfg["delta_bar"][i, c] = pr.delta_bar
                cfg["delta_tilde"][i, c] = pr.delta_tilde
                cfg["psi_bar"][i, c] = pr.psi_bar
                cfg["psi_tilde"][i, c] = pr.psi_tilde
                cfg["J"][i, c] = cls.file_mb
                cfg["alpha"][i, c] = spec.alpha
                cfg["r_max"][i, c] = r_max
                cfg["h_k"][i, c, : len(h_k)] = h_k
                cfg["h_n"][i, c, : len(h_n)] = h_n
        return cfg

    def run(self, cases: list[SchedCase], count: int, *,
            stream=None) -> SchedResult:
        """Evaluate every joint grid point over ``count`` merged arrivals.

        Host side: per-case RNG streams generate merged interarrivals,
        exponential draws and class-id streams (same plumbing as the fleet:
        one ``default_rng(seed)`` per case). Device side: ceil(G / chunk)
        vmapped launches hitting the shape-bucket cache.

        ``stream`` (True or a :class:`repro.fleet.shard.StreamSpec`) folds
        each chunk into the per-class frontier statistics on device instead
        of stacking the (G, count) block — see :mod:`repro.fleet.shard`.
        """
        if not cases:
            raise ValueError("empty case grid")
        import jax.numpy as jnp

        from repro.fleet.shard import StreamedStats, resolve_stream

        spec = resolve_stream(stream)
        traces0, launches0 = self.stats.traces, self.stats.launches
        C = max(len(case.mix.classes) for case in cases)
        n_max = max(c.n_max for case in cases for c in case.mix.classes)
        hk_len = max(c.k_max for case in cases for c in case.mix.classes) + 1
        hn_len = n_max + 1
        key = self.bucket_key(len(cases), count, C, n_max, hk_len, hn_len)
        chunk, T_b = key[0], key[1]

        cfg = self._stack_cfg(cases, C, hk_len, hn_len)
        G = len(cases)
        collect = obs.enabled()
        if collect:
            cfg["obs_count"] = np.full(G, count, np.int32)
        # Materialized runs keep the class-id streams for the per-class
        # reductions; streamed runs fold them per chunk and never stack them.
        ids_full = None if spec else np.zeros((G, count), np.int32)

        def chunk_streams(idx):
            inter = np.zeros((len(idx), T_b), np.float32)
            ids = np.zeros((len(idx), T_b), np.int32)
            exps = np.zeros((len(idx), T_b, n_max), np.float32)
            for j, i in enumerate(idx):
                if j and i == idx[0]:  # tail pad: repeat the chunk's row 0
                    inter[j], ids[j], exps[j] = inter[0], ids[0], exps[0]
                    continue
                case = cases[i]
                rng = np.random.default_rng(case.seed)
                case_n_max = max(c.n_max for c in case.mix.classes)
                it, ex, ci = case.mix.multiclass_device_arrays(
                    rng, count, case_n_max)
                inter[j, :count] = it
                ids[j, :count] = ci
                # Narrower classes leave trailing Exp columns at zero; the
                # scan masks draws at j >= k, so the padding never enters.
                exps[j, :count, :case_n_max] = ex
                if ids_full is not None:
                    ids_full[i] = ci
            return inter, ids, exps

        fn = self._fn_for(key, collect)
        fold = (
            multiclass_fold(int(count * spec.warmup_frac), C, count)
            if spec else None
        )
        stacked = self._launch_chunks(fn, cfg, chunk_streams, G, chunk, count,
                                      fold=fold)
        if not spec:
            stacked["cls_ids"] = jnp.asarray(ids_full)
        return SchedResult(
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
