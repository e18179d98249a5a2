"""repro.obs — unified telemetry across the serving tower and sweep engines.

Layers (see ROADMAP "Conventions"):

* device-resident metrics — :class:`MetricsBuf` pytrees threaded through
  the jitted hot paths and folded per chunk (no host syncs);
* time-resolved timelines — :class:`TimelineBuf` ring/windowed pytrees of
  per-round / per-window series (arrival rate, backlog, pick, served) and
  delay-histogram deltas; windowed percentiles are recoverable host-side;
* SLO / convergence monitoring — :class:`SLOSpec` burn rates and
  pick-settling over timeline snapshots, with structured NDJSON events
  (:class:`EventLog`) mirrored into the profiler's trace as instant marks;
* host spans — :func:`span` / :func:`instant` around compile / launch /
  admission / task / decode boundaries, written into the JAX profiler's
  trace on the device trace's clock (:mod:`repro.obs.trace`);
* shared compile accounting — :class:`CompileStats` behind every engine's
  ``stats`` object, queryable in one shot via :func:`compile_snapshot`;
* launch profiling — :func:`profile_launch` cost-model + wallclock records
  registered into the same compile registry;
* dashboards — :func:`ascii_dashboard` / :func:`html_report` over the
  timeline snapshots, SLO reports, and profiler tables;
* per-request flight recorder — :class:`FlightLog` over the exact engine's
  static ``flight=True`` records (simulated-time Chrome traces, NDJSON
  streams, p99 exemplar mining) and :class:`FlightRing` for the serving
  loop's per-round phase breakdown.

The device-resident planes are gated on ``REPRO_OBS=1`` (or
:func:`set_enabled`); disabled, they cost one branch per site and change no
compiled graph. Host spans are gated by the profiler's session alone.
"""
from repro.obs.state import enabled, set_enabled
from repro.obs.compile import CompileStats, compile_snapshot, register_stats
from repro.obs.metrics import (
    PICK_BINS,
    MetricsBuf,
    sweep_point_metrics,
    to_prometheus,
    valid_mask,
)
from repro.obs.timeline import (
    DELAY_BINS,
    TIMELINE_SLOTS,
    TimelineBuf,
    delay_bucket,
    hist_percentile,
    rolling_percentile,
    sweep_timeline,
    timeline_window,
)
from repro.obs.slo import (
    EventLog,
    SLOSpec,
    burn_rate,
    convergence,
    slo_report,
)
from repro.obs.profile import (
    format_profile,
    profile_launch,
    profile_snapshot,
    reset_profiles,
)
from repro.obs.dashboard import ascii_dashboard, html_report, sparkline
from repro.obs.flight import (
    FLIGHT_SCHEMA,
    FlightLog,
    FlightRing,
    exemplar_panel,
    oracle_task_rows,
    write_trace_doc,
)
from repro.obs.trace import instant, span
from repro.obs.meta import SCHEMA_VERSION, git_rev, run_meta

__all__ = [
    "enabled",
    "set_enabled",
    "CompileStats",
    "compile_snapshot",
    "register_stats",
    "MetricsBuf",
    "PICK_BINS",
    "sweep_point_metrics",
    "valid_mask",
    "to_prometheus",
    "TimelineBuf",
    "TIMELINE_SLOTS",
    "DELAY_BINS",
    "delay_bucket",
    "hist_percentile",
    "rolling_percentile",
    "sweep_timeline",
    "timeline_window",
    "SLOSpec",
    "EventLog",
    "burn_rate",
    "convergence",
    "slo_report",
    "profile_launch",
    "profile_snapshot",
    "format_profile",
    "reset_profiles",
    "ascii_dashboard",
    "html_report",
    "sparkline",
    "FLIGHT_SCHEMA",
    "FlightLog",
    "FlightRing",
    "exemplar_panel",
    "oracle_task_rows",
    "write_trace_doc",
    "span",
    "instant",
    "SCHEMA_VERSION",
    "git_rev",
    "run_meta",
]
