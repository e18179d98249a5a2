"""Sharded, streaming scale-out for the chunked sweep engines.

Two orthogonal capabilities, shared by :class:`repro.fleet.FleetSweep`,
:class:`repro.sched.SchedSweep` and :class:`repro.taskq.TaskqSweep` through
their common :class:`repro.fleet.sweep.ChunkedVmapSweep` base:

**Grid sharding** (:func:`resolve_grid_mesh` + :func:`shard_grid`): the
stacked grid-case axis of each chunked launch is partitioned across a 1-D
device mesh with ``shard_map`` — per-case config arrays and RNG streams are
sharded on the grid axis, while grid-shared broadcast operands (the taskq
trace pools, threshold tables passed via ``in_axes=None``) are replicated
to every device. Each device runs the same vmapped scan over its slice of
the chunk, so a D-device mesh cuts per-launch wall clock ~D× without
changing a single drawn value: grid rows are independent, which makes the
sharded result bit-exact against the single-device path (asserted in
``tests/test_shard.py``). The compile cache stays pow2-bucketed and is
keyed additionally on the mesh shape.

**Streaming frontier reductions** (:class:`StreamSpec` + :class:`StreamedStats`):
instead of materializing the whole (G, T) per-request output block and
reducing it afterwards, a streamed run folds every chunk's scan outputs
into fixed-size per-row frontier statistics on device — the fused reduction
kernels in :mod:`repro.fleet.stats` — and drops the (chunk, T) block before
the next launch. Peak memory becomes O(chunk × T) per launch plus O(G) for
the carried statistics, instead of O(G × T) for the stacked result, which
is what lets ~1e5-point grids run at all. Because the streamed fold runs
the *same* jitted reduction the materialized frontier uses (and per-row
reductions are invariant to the leading batch size), the streamed
statistics are bit-exact equals of the materialized ones;
``frontier_points`` / ``convergence_stats`` / ``multiclass_points`` and the
artifact writers consume a streamed result through the same API.

The observability side-channels ride both capabilities unchanged: per-case
:class:`repro.obs.MetricsBuf` rows fold per chunk (cut → row-reduce →
merge) while per-case :class:`repro.obs.TimelineBuf` timelines keep their
case axis (cut → concat).  Both are per-slot/per-case reductions —
invariant to the leading batch size and to where the grid axis is split —
so streamed and mesh-sharded runs carry metrics AND timelines bit-exactly
equal to the materialized single-device path (asserted in
``tests/test_obs.py`` / ``tests/test_shard.py``).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro import obs


def resolve_grid_mesh(mesh):
    """Normalize a sweep's ``mesh`` argument to a 1-D jax Mesh (or None).

    Accepts ``None`` (single-device path, never touches jax device state),
    an int device count (first n devices via :func:`repro.launch.mesh.
    make_grid_mesh`), or an existing 1-D Mesh of any axis name.
    """
    if mesh is None:
        return None
    if isinstance(mesh, int):
        from repro.launch.mesh import make_grid_mesh

        return make_grid_mesh(mesh)
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"sweep meshes are 1-D (the grid axis); got axes {mesh.axis_names}"
        )
    return mesh


def shard_grid(fn, mesh, in_axes: tuple):
    """Wrap a whole-chunk vmapped launch body in ``shard_map`` over ``mesh``.

    ``in_axes`` is the launch's vmap spec: axis-0 entries (per-case config
    pytrees, RNG streams) shard along the mesh's grid axis; ``None`` entries
    (grid-shared broadcast operands, e.g. trace pools) replicate whole to
    every device — mirroring the taskq ``in_axes=None`` convention. Outputs
    come back sharded on the grid axis. The wrapped body must consume
    positional args matching ``in_axes`` one-for-one.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    in_specs = tuple(P(axis) if ax == 0 else P() for ax in in_axes)
    # Rows are independent and the body runs no collectives, so there is no
    # cross-device variance to track; the scan carries in the engines build
    # their initial state from constants, which vma checking would reject.
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=P(axis),
                         check_vma=False)


@dataclasses.dataclass(frozen=True)
class StreamSpec:
    """Ask a sweep run to stream: fold each chunk into frontier statistics.

    The warmup cut must be fixed before the first chunk is folded, so it is
    part of the run request rather than a reduction-time argument; the
    frontier consumers validate that their ``warmup_frac`` lands on the same
    cut (:meth:`StreamedStats.require`).
    """

    warmup_frac: float = 0.05


class StreamedStats:
    """Running frontier-reduction state carried by a streamed sweep result.

    Holds the per-row statistics (name → (G,) / (G, C) numpy arrays) that
    the per-chunk folds accumulated, plus the warmup cut they were folded
    at. ``repro.fleet.frontier`` / ``repro.sched.frontier`` consume this in
    place of the (G, T) output block — same API surface, no materialized
    grid.
    """

    def __init__(self, warmup_frac: float, count: int, red: dict):
        self.warmup_frac = float(warmup_frac)
        self.count = int(count)
        # The streamed path's one device→host download of the folded stats.
        with obs.span("sweep.stream_finalize", stats=len(red)):
            self.red = {name: np.asarray(v) for name, v in red.items()}

    @property
    def warmup(self) -> int:
        return int(self.count * self.warmup_frac)

    def require(self, warmup_frac: float) -> dict:
        """The streamed statistics, checked against a requested warmup cut.

        Streaming fixes the cut at launch time; asking the frontier for a
        different one afterwards cannot be served from the carry.
        """
        if int(self.count * warmup_frac) != self.warmup:
            raise ValueError(
                f"result was streamed at warmup_frac={self.warmup_frac} "
                f"(cut {self.warmup}); re-run the sweep with "
                f"StreamSpec(warmup_frac={warmup_frac}) to reduce at a "
                "different cut"
            )
        return self.red


def resolve_stream(stream) -> StreamSpec | None:
    """Normalize a run's ``stream`` argument: None/False | True | StreamSpec."""
    if not stream:
        return None
    return stream if isinstance(stream, StreamSpec) else StreamSpec()
