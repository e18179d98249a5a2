"""repro.fleet — on-device fleet simulator for TOFEC experiment grids.

The paper's evaluation story (Fig.1/7/8, and the wide λ-grids of the
journal version arXiv:1403.5007) is a sweep over (arrival rate × policy ×
seed). This package evaluates entire such grids in a handful of jitted
launches:

* :mod:`repro.fleet.workloads` — a workload-generator family (Poisson,
  MMPP bursty, diurnal, flash-crowd, piecewise trace replay, multi-tenant
  class mixes) producing device-ready arrival arrays AND host event-sim
  arrival times from the same spec.
* :mod:`repro.fleet.sweep` — ``vmap``ped :func:`repro.core.jax_sim.
  tofec_scan_core` across a stacked config axis with memory-bounded
  chunked batching and shape-bucketed jit caching.
* :mod:`repro.fleet.frontier` — on-device reductions to throughput-delay
  frontiers, delay percentiles, capacity estimates, adaptation-convergence
  stats, and the ``BENCH_fleet.json`` artifact writer.
* :mod:`repro.fleet.shard` — ``shard_map`` scale-out of the grid axis
  across a device mesh plus streaming per-chunk frontier reductions
  (``run(..., stream=...)``), shared with :mod:`repro.sched` and
  :mod:`repro.taskq` through the common chunked-sweep base.
"""

from repro.core.controller import PolicySpec
from repro.fleet.frontier import (
    FrontierPoint,
    capacity_estimates,
    convergence_stats,
    frontier,
    frontier_points,
    headline_ratios,
    write_fleet_artifact,
)
from repro.fleet.shard import (
    StreamedStats,
    StreamSpec,
    resolve_grid_mesh,
)
from repro.fleet.sweep import (
    FleetSweep,
    SweepCase,
    SweepResult,
    grid_cases,
    tenant_cases,
)
from repro.fleet.workloads import (
    DiurnalWorkload,
    FlashCrowdWorkload,
    MMPPWorkload,
    PiecewiseWorkload,
    PoissonWorkload,
    TenantMix,
    Workload,
)

__all__ = [
    "Workload",
    "PoissonWorkload",
    "MMPPWorkload",
    "DiurnalWorkload",
    "FlashCrowdWorkload",
    "PiecewiseWorkload",
    "TenantMix",
    "FleetSweep",
    "SweepCase",
    "SweepResult",
    "PolicySpec",
    "grid_cases",
    "tenant_cases",
    "FrontierPoint",
    "frontier",
    "frontier_points",
    "capacity_estimates",
    "convergence_stats",
    "headline_ratios",
    "write_fleet_artifact",
    "StreamSpec",
    "StreamedStats",
    "resolve_grid_mesh",
]
