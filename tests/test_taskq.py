"""repro.taskq: the exact task-level engine's draw-for-draw parity with the
discrete-event oracle over shared trace pools, device/host pool-read and
Greedy-selection parity, the bounded-compile claim for heterogeneous
(threshold + greedy) grids, and the BENCH_taskq.json artifact."""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import PAPER_READ_3MB, RequestClass, StaticPolicy, TOFECPolicy, build_class_plan
from repro.core.controller import GreedyPolicy
from repro.core.simulator import simulate
from repro.core.traces import TraceStore
from repro.core.controller import policy_tables
from repro.fleet import PolicySpec, grid_cases
from repro.fleet.stats import masked_percentiles
from repro.taskq import (
    TaskqSweep,
    greedy_select,
    taskq_streams,
    write_taskq_artifact,
)

CLS = RequestClass("read3mb", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12)
L = 16
SIZES = tuple(CLS.file_mb / k for k in range(1, CLS.k_max + 1))


def make_pools(correlation: float, seed: int = 3, samples: int = 2048):
    store = TraceStore.generate(
        PAPER_READ_3MB, SIZES, threads=CLS.n_max, samples=samples,
        correlation=correlation, seed=seed,
    )
    return store, store.device_pools(n_max=CLS.n_max)


def run_host(case, count, dp, policy):
    """The event oracle on the same draws a TaskqSweep point consumes."""
    inter, idx = taskq_streams(case, count, dp.n_rows)
    arrivals = np.cumsum(inter.astype(np.float64))
    return simulate(
        policy, arrivals, dp.host_sampler(CLS.file_mb, idx), L=L, warmup_frac=0.0
    )


# ---------------------------------------------------------------------------
# Shared trace pools: device and host read identical values
# ---------------------------------------------------------------------------


def test_device_pools_and_host_sampler_read_identical_values():
    store, dp = make_pools(correlation=0.14)
    assert dp.pools.shape == (len(SIZES), 2048, CLS.n_max)
    assert dp.pools.dtype == np.float32 and dp.sizes_mb.dtype == np.float32
    rng = np.random.default_rng(0)
    indices = rng.integers(dp.n_rows, size=64)
    sampler = dp.host_sampler(CLS.file_mb, indices)
    for i in [0, 7, 31, 63]:
        for k, n in [(1, 2), (3, 6), (6, 12)]:
            host = sampler.sample_indexed(i, k, n)
            s = dp.pool_index(CLS.file_mb, k)
            dev = np.asarray(jnp.asarray(dp.pools)[s, indices[i], :n])
            np.testing.assert_array_equal(host.astype(np.float32), dev)
            # And both equal the originating store pool row.
            np.testing.assert_array_equal(
                dev, store.pools[s][indices[i], :n].astype(np.float32)
            )


def test_device_pools_validates_width_and_rows():
    store, _ = make_pools(correlation=0.0, samples=128)
    with pytest.raises(ValueError):
        store.device_pools(n_max=CLS.n_max + 1)
    with pytest.raises(ValueError):
        store.device_pools(n_max=CLS.n_max, size=256)
    small = store.device_pools(n_max=4, size=32)
    assert small.pools.shape == (len(SIZES), 32, 4)


def test_shared_key_correlation_survives_export():
    _, dp = make_pools(correlation=0.14)
    pool = dp.pools[0]  # (P, W) at the largest chunk size
    c = np.corrcoef(pool.T)
    off = c[~np.eye(c.shape[0], dtype=bool)]
    assert off.mean() > 0.05, off.mean()


# ---------------------------------------------------------------------------
# Greedy parity: device select vs host GreedyPolicy
# ---------------------------------------------------------------------------


def test_greedy_select_matches_host_policy_on_randomized_states():
    rng = np.random.default_rng(42)
    checked = 0
    for _ in range(200):
        k_max = int(rng.integers(1, 9))
        r_max = float(rng.choice([1.5, 2.0, 2.5, 3.0]))
        idle = int(rng.integers(-2, 2 * L + 1))
        q = int(rng.integers(0, 50))
        host = GreedyPolicy(k_max, r_max).select(q=q, idle=idle)
        n_d, k_d = greedy_select(
            jnp.float32(q), jnp.int32(idle), jnp.int32(k_max), jnp.float32(r_max)
        )
        assert (int(n_d), int(k_d)) == host, (q, idle, k_max, r_max)
        checked += 1
    assert checked == 200


# ---------------------------------------------------------------------------
# Exactness: engine vs event oracle on shared pools
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,k,lam,correlation",
    [
        (1, 1, 8.0, 0.0),    # basic code, unique-key placement
        (6, 3, 30.0, 0.0),   # mid code under load, unique-key
        (12, 6, 20.0, 0.14),  # latency-optimal code, shared-key copula
        (4, 2, 45.0, 0.14),  # heavy load, shared-key
    ],
)
def test_engine_matches_event_oracle_draw_for_draw(n, k, lam, correlation):
    """With shared pre-sampled pools, per-request (total, queueing, service)
    delays equal the discrete-event oracle within float32 tolerance — the
    exact k-of-n + cancellation dynamics, not the fluid approximation."""
    _, dp = make_pools(correlation)
    count = 1200
    case = grid_cases([lam], [PolicySpec.static(n, k)], [7], CLS, L)[0]
    res = TaskqSweep(chunk=4).run([case], count, dp)
    host = run_host(case, count, dp, StaticPolicy(n, k))
    assert len(host.stats) == count
    out = res.to_numpy()
    np.testing.assert_allclose(out["total"][0], host.totals(), rtol=1e-3, atol=2e-3)
    np.testing.assert_allclose(out["queueing"][0], host.queueing(), rtol=1e-3, atol=2e-3)
    np.testing.assert_allclose(out["service"][0], host.service(), rtol=1e-3, atol=2e-3)
    assert (out["n"][0] == n).all() and (out["k"][0] == k).all()


def test_engine_exact_when_n_exceeds_thread_count():
    """n > L: the excess tasks queue for threads freed by their own
    siblings' completions (and are cancelled with the rest at the k-th
    completion) — the pass-1 feedback makes this exact too."""
    _, dp = make_pools(correlation=0.14)
    count = 800
    L_small = 8
    case = grid_cases([15.0], [PolicySpec.static(12, 6)], [9], CLS, L_small)[0]
    res = TaskqSweep(chunk=4).run([case], count, dp)
    inter, idx = taskq_streams(case, count, dp.n_rows)
    arrivals = np.cumsum(inter.astype(np.float64))
    host = simulate(StaticPolicy(12, 6), arrivals,
                    dp.host_sampler(CLS.file_mb, idx), L=L_small, warmup_frac=0.0)
    out = res.to_numpy()
    np.testing.assert_allclose(out["total"][0], host.totals(), rtol=1e-3, atol=2e-3)
    np.testing.assert_allclose(out["queueing"][0], host.queueing(), rtol=1e-3, atol=2e-3)


def test_engine_tracks_adaptive_trajectories_of_the_oracle():
    """Beyond static codes: the exact backlog/idle observables let TOFEC and
    Greedy reproduce the oracle's per-request (n, k) decision sequence
    almost everywhere (fp boundary ties at threshold crossings excepted)."""
    _, dp = make_pools(correlation=0.0)
    count = 1200
    # TOFEC thresholds on the true queue length.
    case = grid_cases([35.0], [PolicySpec.tofec()], [5], CLS, L)[0]
    res = TaskqSweep(chunk=4).run([case], count, dp)
    host = run_host(case, count, dp, TOFECPolicy([build_class_plan(CLS, L)]))
    out = res.to_numpy()
    assert (out["n"][0] == host.ns()).mean() > 0.99
    assert (out["k"][0] == host.ks()).mean() > 0.99
    np.testing.assert_allclose(
        out["total"][0].mean(), host.totals().mean(), rtol=1e-2
    )
    # Greedy on the true idle-thread count — the policy the fluid sweeps
    # could never run.
    case = grid_cases([40.0], [PolicySpec.greedy()], [11], CLS, L)[0]
    res = TaskqSweep(chunk=4).run([case], count, dp)
    host = run_host(case, count, dp, GreedyPolicy(CLS.k_max, CLS.r_max))
    out = res.to_numpy()
    assert (out["n"][0] == host.ns()).mean() > 0.99
    assert (out["k"][0] == host.ks()).mean() > 0.99


def test_chunk_padding_keeps_results_exact():
    """Different chunkings of the same grid are bit-identical (the fleet's
    tail-padding guarantee holds for the broadcast-pool launch path too)."""
    _, dp = make_pools(correlation=0.14)
    cases = grid_cases([10.0, 30.0, 50.0], [PolicySpec.tofec()], [0, 1], CLS, L)
    a = TaskqSweep(chunk=4).run(cases, 600, dp).to_numpy()  # 6 = 4 + 2(pad)
    b = TaskqSweep(chunk=8).run(cases, 600, dp).to_numpy()  # one launch
    for name in ("total", "queueing", "service", "n", "k"):
        np.testing.assert_array_equal(a[name], b[name])


# ---------------------------------------------------------------------------
# Shape buckets / compile counts
# ---------------------------------------------------------------------------


def test_heterogeneous_policy_sweep_compiles_once_per_bucket():
    """A ≥32-case grid mixing threshold policies AND greedy runs in ONE
    compilation; same-bucket re-runs are compile-free; a new time bucket
    compiles once more — TaskqSweep.stats pins it."""
    _, dp = make_pools(correlation=0.0)
    sweep = TaskqSweep(chunk=16, t_floor=512)
    lams = np.linspace(6.0, 48.0, 4)
    policies = [PolicySpec.tofec(), PolicySpec.static(1, 1),
                PolicySpec.static(12, 6), PolicySpec.greedy()]
    cases = grid_cases(lams, policies, [0, 1], CLS, L)
    assert len(cases) == 32

    res = sweep.run(cases, count=400, pools=dp)
    assert res.compiles == 1, res.compiles
    assert res.launches == 2  # 32 points / chunk 16

    res2 = sweep.run(cases[:12], count=500, pools=dp)  # same 512 bucket
    assert res2.compiles == 0
    res3 = sweep.run(cases[:4], count=600, pools=dp)  # new time bucket
    assert res3.compiles == 1
    assert sweep.stats.traces == 2 and sweep.stats.cases == 32 + 12 + 4


def test_greedy_rejected_by_fleet_tables():
    with pytest.raises(ValueError, match="taskq"):
        policy_tables(PolicySpec.greedy(), CLS, L)


def test_mixed_L_rejected():
    _, dp = make_pools(correlation=0.0)
    cases = grid_cases([10.0], [PolicySpec.tofec()], [0], CLS, L)
    cases += grid_cases([10.0], [PolicySpec.tofec()], [0], CLS, L=8)
    with pytest.raises(ValueError, match="share L"):
        TaskqSweep().run(cases, 256, dp)


# ---------------------------------------------------------------------------
# Frontier reuse + artifact
# ---------------------------------------------------------------------------


def test_taskq_artifact_orders_policies_like_the_paper(tmp_path):
    """The exact engine's frontier reproduces the TOFEC-vs-static story and
    lands in BENCH_taskq.json via the fleet's reductions."""
    _, dp = make_pools(correlation=0.0)
    lams = np.linspace(6.0, 48.0, 4)
    policies = [PolicySpec.tofec(), PolicySpec.static(1, 1),
                PolicySpec.static(12, 6), PolicySpec.greedy()]
    res = TaskqSweep(chunk=16).run(grid_cases(lams, policies, [1], CLS, L),
                                   1500, dp)
    path = tmp_path / "BENCH_taskq.json"
    art = write_taskq_artifact(str(path), res)
    on_disk = json.loads(path.read_text())
    assert on_disk["schema"] == "repro.taskq/BENCH_taskq/v1"
    assert on_disk["grid_size"] == 16 and len(on_disk["points"]) == 16

    from repro.fleet import frontier, frontier_points

    by = frontier(frontier_points(res))
    assert set(by) == {"tofec", "static(1,1)", "static(12,6)", "greedy"}
    # Light load: high-chunk codes (static(12,6), TOFEC, greedy) all beat
    # the basic code's mean delay.
    light = {name: pts[0].mean for name, pts in by.items()}
    assert light["static(12,6)"] < light["static(1,1)"]
    assert light["tofec"] < light["static(1,1)"]
    assert light["greedy"] < light["static(1,1)"]
    for p in frontier_points(res):
        assert p.p50 <= p.p90 <= p.p95 <= p.p99


def test_masked_percentiles_shared_helper_matches_numpy():
    """The hoisted fleet/sched/taskq percentile helper is the lower-method
    order statistic, masked and unmasked."""
    rng = np.random.default_rng(1)
    x = rng.exponential(1.0, size=(3, 257)).astype(np.float32)
    qs = [50.0, 90.0, 95.0, 99.0]
    got = np.asarray(masked_percentiles(jnp.asarray(x), qs))
    want = np.percentile(x, qs, axis=1, method="lower").T
    np.testing.assert_allclose(got, want, rtol=1e-6)
    mask = x < 1.5
    got_m = np.asarray(masked_percentiles(jnp.asarray(x), qs, jnp.asarray(mask)))
    for g in range(3):
        want_m = np.percentile(x[g][mask[g]], qs, method="lower")
        np.testing.assert_allclose(got_m[g], want_m, rtol=1e-6)
    empty = np.zeros_like(mask)
    # An all-false mask has no order statistic: NaN, not a clamped gather
    # (the edge-case contract pinned in tests/test_shard.py).
    assert np.all(np.isnan(np.asarray(
        masked_percentiles(jnp.asarray(x), qs, jnp.asarray(empty)))))
