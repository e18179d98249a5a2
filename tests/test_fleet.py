"""repro.fleet: workload generators, the vmapped sweep's fidelity to the
single jitted scan AND the discrete-event oracle, the bounded-compile
claim for heterogeneous grids, and the BENCH_fleet.json frontier artifact
(TOFEC-vs-static delay/capacity ordering of Fig.7/8)."""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    PAPER_READ_3MB,
    PAPER_WRITE_3MB,
    FixedKAdaptivePolicy,
    RequestClass,
    StaticPolicy,
    TofecTables,
    TOFECPolicy,
    build_class_plan,
    tofec_threshold_step,
)
from repro.core.controller import fixedk_tables, static_tables
from repro.core.jax_sim import JaxSimParams, simulate_tofec_scan
from repro.core.simulator import piecewise_poisson_arrivals, poisson_arrivals, simulate
from repro.core.traces import TraceSampler
from repro.fleet import (
    DiurnalWorkload,
    FlashCrowdWorkload,
    FleetSweep,
    MMPPWorkload,
    PiecewiseWorkload,
    PoissonWorkload,
    PolicySpec,
    TenantMix,
    capacity_estimates,
    convergence_stats,
    frontier_points,
    grid_cases,
    tenant_cases,
    write_fleet_artifact,
)

CLS = RequestClass("read3mb", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12)
L = 16
PLAN = build_class_plan(CLS, L)
SAMPLER = TraceSampler(PAPER_READ_3MB, CLS.file_mb)


# ---------------------------------------------------------------------------
# Workload generators
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "wl",
    [
        PoissonWorkload(20.0),
        MMPPWorkload(rates=(8.0, 40.0), dwell=(6.0, 2.0)),
        DiurnalWorkload(base=20.0, amplitude=0.6, period=60.0),
        PiecewiseWorkload(((30.0, 10.0), (30.0, 40.0))),
    ],
)
def test_workload_mean_rate_and_device_arrays(wl):
    rng = np.random.default_rng(0)
    count = 4000
    inter, exps = wl.device_arrays(rng, count, CLS.n_max)
    assert inter.shape == (count,) and inter.dtype == np.float32
    assert exps.shape == (count, CLS.n_max) and exps.dtype == np.float32
    assert np.all(inter >= 0.0)
    # Empirical rate within 15% of the spec's mean rate.
    emp = count / inter.sum()
    assert 0.85 * wl.mean_rate() < emp < 1.15 * wl.mean_rate(), (emp, wl)
    # Event-sim form: increasing absolute times at a consistent rate.
    times = wl.arrival_times(np.random.default_rng(1), 120.0)
    assert np.all(np.diff(times) > 0.0) and times[-1] < 120.0
    emp_t = len(times) / 120.0
    assert 0.7 * wl.mean_rate() < emp_t < 1.3 * wl.mean_rate()


def test_mmpp_is_bursty():
    """Burstiness shows up as interarrival CoV > 1 (Poisson has CoV = 1)."""
    rng = np.random.default_rng(2)
    inter = MMPPWorkload(rates=(4.0, 80.0), dwell=(8.0, 2.0)).interarrivals(rng, 20_000)
    cov = inter.std() / inter.mean()
    assert cov > 1.25, cov


def test_flash_crowd_rate_step():
    """The step is transient (rate reverts to base after t_off), so the
    flash crowd is pinned by its profile, not a single long-run mean."""
    wl = FlashCrowdWorkload(base=10.0, peak=80.0, t_on=50.0, t_off=100.0)
    times = wl.arrival_times(np.random.default_rng(3), 150.0)
    burst = np.sum((times >= 50.0) & (times < 100.0)) / 50.0
    calm = (np.sum(times < 50.0) + np.sum(times >= 100.0)) / 100.0
    assert burst > 4.0 * calm
    inter, exps = wl.device_arrays(np.random.default_rng(4), 2000, CLS.n_max)
    assert inter.shape == (2000,) and exps.shape == (2000, CLS.n_max)
    assert np.all(inter >= 0.0)


def test_piecewise_wrapper_is_draw_for_draw_compatible():
    """simulator.piecewise_poisson_arrivals is now a thin wrapper: identical
    output for the identical RNG stream (Fig.10 stays reproducible)."""
    rates = [(200.0, 10.0), (200.0, 70.0), (200.0, 10.0)]
    a = piecewise_poisson_arrivals(np.random.default_rng(10), rates)
    b = PiecewiseWorkload(tuple(rates)).arrival_times(np.random.default_rng(10))
    np.testing.assert_allclose(a, b)
    assert a[-1] < 600.0 and np.sum((a > 200) & (a < 400)) > 10_000


def test_tenant_mix_split_and_cls_ids():
    small = RequestClass("read1mb", 1.0, PAPER_READ_3MB, k_max=4, r_max=2.0, n_max=8)
    mix = TenantMix(lam=30.0, classes=(CLS, small), weights=(0.75, 0.25))
    rng = np.random.default_rng(4)
    ids = mix.cls_ids(rng, 8000)
    assert 0.70 < (ids == 0).mean() < 0.80
    split = mix.split()
    assert [c.name for c, _ in split] == ["read3mb", "read1mb"]
    assert np.isclose(sum(w.lam for _, w in split), 30.0)
    # Per-class sub-points ride one heterogeneous sweep (padded tables).
    # quiet=True: the fluid split is deliberate here (repro.sched owns the
    # joint shared-pool path and tenant_cases warns about the approximation).
    res = FleetSweep(chunk=8).run(
        tenant_cases(mix, [PolicySpec.tofec()], [0], L, quiet=True), count=600
    )
    ks = np.asarray(res.out["k"])
    assert int(ks[0].max()) <= CLS.k_max and int(ks[1].max()) <= small.k_max


# ---------------------------------------------------------------------------
# Policy-as-tables encodings
# ---------------------------------------------------------------------------


def test_static_tables_pin_the_code():
    for n, k in [(1, 1), (2, 1), (6, 3), (12, 6), (5, 4)]:
        h_k, h_n, r_max = static_tables(n, k, CLS.k_max, CLS.n_max)
        for q in [0.0, 0.3, 7.0, 1e4]:
            _, n_j, k_j = tofec_threshold_step(
                jnp.float32(q), jnp.float32(q), jnp.asarray(h_k), jnp.asarray(h_n),
                r_max, 0.99,
            )
            assert (int(n_j), int(k_j)) == (n, k), (n, k, q)


def test_fixedk_tables_match_host_policy():
    k = 6
    h_k, h_n, r_max = fixedk_tables(CLS, L, k)
    pol = FixedKAdaptivePolicy(CLS, L, k=k)
    q_ewma = 0.0
    for q in [0.0, 0.5, 1.0, 2.0, 4.0, 9.0, 30.0, 2.0, 0.0]:
        n_host, k_host = pol.select(q=q, idle=0)
        q_ewma, n_j, k_j = tofec_threshold_step(
            jnp.float32(q_ewma), jnp.float32(q), jnp.asarray(h_k), jnp.asarray(h_n),
            r_max, pol.alpha,
        )
        assert (int(n_j), int(k_j)) == (n_host, k_host), q


# ---------------------------------------------------------------------------
# Sweep fidelity
# ---------------------------------------------------------------------------


def test_sweep_row_matches_single_jitted_scan():
    """A fleet grid row must reproduce simulate_tofec_scan on the same
    draws — the vmapped/chunked/padded path adds no semantic drift."""
    lam, seed, count = 18.0, 5, 1200
    cases = grid_cases([lam], [PolicySpec.tofec()], [seed], CLS, L)
    res = FleetSweep(chunk=4).run(cases, count)

    rng = np.random.default_rng(seed)
    inter, exps = PoissonWorkload(lam).device_arrays(rng, count, CLS.n_max)
    ref = simulate_tofec_scan(
        JaxSimParams.from_class(CLS, L), TofecTables.from_plan(PLAN),
        jnp.asarray(inter), jnp.asarray(exps),
    )
    out = res.to_numpy()
    assert (out["n"][0] == np.asarray(ref["n"])).mean() >= 0.999
    assert (out["k"][0] == np.asarray(ref["k"])).mean() >= 0.999
    np.testing.assert_allclose(out["total"][0], np.asarray(ref["total"]),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize(
    "lam,policy,host_policy,tol",
    [
        (5.0, PolicySpec.tofec(), None, 0.30),
        (5.0, PolicySpec.static(1, 1), StaticPolicy(1, 1), 0.15),
        (25.0, PolicySpec.static(6, 3), StaticPolicy(6, 3), 0.15),
        (50.0, PolicySpec.tofec(), None, 0.30),
    ],
)
def test_sweep_cross_validates_against_event_oracle(lam, policy, host_policy, tol):
    """≥3 (λ, policy) grid points: fleet mean total delay within tolerance
    of the discrete-event simulator (the §IV-A approximation error band)."""
    count = 3000
    res = FleetSweep().run(grid_cases([lam], [policy], [3], CLS, L), count)
    fleet_mean = frontier_points(res)[0].mean

    rng = np.random.default_rng(7)
    arr = poisson_arrivals(rng, lam, count)
    host = host_policy if host_policy is not None else TOFECPolicy([PLAN])
    event = simulate(host, arr, SAMPLER, L=L, seed=8)
    event_mean = float(event.totals().mean())
    assert abs(fleet_mean - event_mean) / event_mean < tol, (fleet_mean, event_mean)


# ---------------------------------------------------------------------------
# Shape buckets / compile counts
# ---------------------------------------------------------------------------


def test_sweep_compile_count_bounded_on_heterogeneous_grid():
    """A ≥64-point heterogeneous (λ × policy × seed) grid runs in ONE
    compilation; re-runs and same-bucket grids stay compile-free; only a
    bucket change (different T bucket) compiles again."""
    sweep = FleetSweep(chunk=16, t_floor=512)
    lams = np.linspace(4.0, 64.0, 8)
    policies = [PolicySpec.tofec(), PolicySpec.static(1, 1),
                PolicySpec.static(12, 6), PolicySpec.fixedk(6)]
    cases = grid_cases(lams, policies, [0, 1], CLS, L)
    assert len(cases) == 64

    res = sweep.run(cases, count=500)
    assert res.compiles == 1, res.compiles
    assert res.launches == 4  # 64 points / chunk 16: memory-bounded batching

    # Same bucket (count 500 vs 400 both pad to 512; different grid subset).
    res2 = sweep.run(cases[:40], count=400)
    assert res2.compiles == 0
    # New time bucket compiles once more.
    res3 = sweep.run(cases[:8], count=600)
    assert res3.compiles == 1
    assert sweep.stats.traces == 2 and sweep.stats.cases == 64 + 40 + 8


def test_sweep_chunk_padding_keeps_results_exact():
    """The repeated-row padding of the tail chunk never leaks into results:
    the same grid swept with different chunkings is identical."""
    cases = grid_cases([6.0, 30.0, 55.0], [PolicySpec.tofec()], [0, 1], CLS, L)
    a = FleetSweep(chunk=4).run(cases, count=700).to_numpy()   # 6 = 4 + 2(pad)
    b = FleetSweep(chunk=8).run(cases, count=700).to_numpy()   # one launch
    for name in ("total", "queueing", "service", "n", "k"):
        np.testing.assert_array_equal(a[name], b[name])


# ---------------------------------------------------------------------------
# Frontier reductions + artifact
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def frontier_sweep():
    lams = np.linspace(5.0, 65.0, 6)
    policies = [PolicySpec.tofec(), PolicySpec.static(1, 1), PolicySpec.static(2, 1),
                PolicySpec.static(6, 3), PolicySpec.static(12, 6)]
    return FleetSweep().run(grid_cases(lams, policies, [1], CLS, L), count=2500)


def test_frontier_artifact_reproduces_paper_ordering(frontier_sweep, tmp_path):
    """One ≥64-point-capable launch family → BENCH_fleet.json with the
    TOFEC-vs-static delay AND capacity ordering of Fig.7/8."""
    path = tmp_path / "BENCH_fleet.json"
    art = write_fleet_artifact(str(path), frontier_sweep)
    on_disk = json.loads(path.read_text())
    assert on_disk["schema"] == "repro.fleet/BENCH_fleet/v1"
    assert on_disk["grid_size"] == 30 and len(on_disk["points"]) == 30

    h = art["headline"]
    # Delay ordering: TOFEC beats the throughput-optimal basic code at
    # light load by a wide margin (paper: ~2.5x).
    assert h["delay_gain_vs_basic"] > 1.5
    # Capacity ordering: TOFEC's supportable rate beats the latency-optimal
    # static code by a wide margin (paper: ~3x).
    assert h["capacity_gain_vs_latency_optimal"] > 1.5
    caps = art["capacity_req_s"]
    assert caps["tofec"] > caps["static(12,6)"]
    assert caps["static(1,1)"] > caps["static(6,3)"] > caps["static(12,6)"]


def test_frontier_percentiles_and_k_adaptation(frontier_sweep):
    pts = frontier_points(frontier_sweep)
    for p in pts:
        assert p.p50 <= p.p90 <= p.p95 <= p.p99
        assert 1.0 <= p.mean_k <= CLS.k_max and p.mean_k <= p.mean_n
    tofec = sorted((p for p in pts if p.policy == "tofec"), key=lambda p: p.lam)
    # Corollary 1: chunking backs off as load grows (Fig.8's story).
    assert tofec[0].mean_k > tofec[-1].mean_k + 1.0


def test_convergence_stats_static_settles_instantly(frontier_sweep):
    stats = convergence_stats(frontier_sweep)
    assert len(stats) == len(frontier_sweep.cases)
    for s in stats:
        if s["policy"].startswith("static("):
            assert s["settle_frac"] == 0.0 and s["modal_frac"] == 1.0
        assert 0.0 <= s["settle_frac"] <= 1.0


def test_capacity_estimates_match_queueing_theory(frontier_sweep):
    """Static-code capacity estimates from the sweep equal L/U from the
    queueing module (the codes' known saturation rates)."""
    from repro.core import queueing

    caps = capacity_estimates(frontier_points(frontier_sweep))
    for (n, k) in [(1, 1), (2, 1), (6, 3)]:
        want = queueing.capacity(PAPER_READ_3MB, CLS.file_mb, k, n / k, L)
        assert abs(caps[f"static({n},{k})"] - want) / want < 1e-3


def test_multi_class_grid_pads_tables_and_exps():
    """Classes with different (k_max, n_max, J) and write-side params share
    one bucketed launch; each row respects its own class's code bounds."""
    wr = RequestClass("write1mb", 1.0, PAPER_WRITE_3MB, k_max=3, r_max=2.0, n_max=6)
    cases = grid_cases([8.0], [PolicySpec.tofec()], [0], CLS, L) + \
        grid_cases([8.0], [PolicySpec.tofec()], [0], wr, L)
    res = FleetSweep(chunk=2).run(cases, count=800)
    assert res.compiles == 1
    out = res.to_numpy()
    assert out["k"][0].max() <= CLS.k_max and out["n"][0].max() <= CLS.n_max
    assert out["k"][1].max() <= wr.k_max and out["n"][1].max() <= wr.n_max
