"""The serving round's fused launch: one jitted launch = TOFEC admission
update + batched MDS decode + bytes→tokens + prefill. Correctness vs the
stored prompts and the host policies, bounded retracing across round sizes
and erasure patterns, and the controller's state and policy swaps."""

import numpy as np
import pytest

import jax

from repro.coding.codec import Codec, pow2_bucket
from repro.coding.layout import SharedKeyLayout
import jax.numpy as jnp

from repro.core import (
    PAPER_READ_3MB,
    PAPER_WRITE_3MB,
    FeedbackPolicy,
    FixedKAdaptivePolicy,
    MPCPolicy,
    MPCTables,
    RequestClass,
    StaticPolicy,
    TOFECPolicy,
    mpc_step_jax,
)
from repro.models import get
from repro.serve import (
    ClosedLoopServer,
    FusedServingStep,
    ServePolicy,
    ServingEngine,
)
from repro.serve import engine as engine_mod
from repro.storage import FaultyStore, LatencyStore, MemoryStore, Proxy

from serve_helpers import closed_loop, greedy_reference

CLS = RequestClass("read3mb", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12)
L = 16
JIT_BACKENDS = ["jnp", "pallas"]
PLEN = 16


def _model(seed):
    arch = get("qwen1.5-0.5b", smoke=True)
    return arch, arch.init(jax.random.key(seed))


def _prompts(arch, rows, seed):
    return np.random.default_rng(seed).integers(0, arch.cfg.vocab, (rows, PLEN)).astype(np.int32)


def _erasing(seed):
    """Store wrapper: reads finish in random order and 10% of chunk reads
    fail, so a read decodes from a random set of k of its n chunks. Rounds
    hold at least two keys: one read loses more than n − k = 4 of its 8
    chunks about once in 2,300, and a round whose reads all fail raises."""
    return lambda inner: FaultyStore(
        LatencyStore(inner, PAPER_READ_3MB, time_scale=1e-4, seed=seed),
        p_fail=0.1, seed=seed)


def _served_prompts_equal(res, truth, keys):
    idx = [keys.index(k) for k in res.served_keys]
    np.testing.assert_array_equal(np.asarray(res.prompts)[: len(idx)], truth[idx])


@pytest.mark.parametrize("backend", JIT_BACKENDS)
def test_fused_decode_matches_oracle_and_policy(backend):
    """Decoded ``prompts`` equal the stored tokens under random erasures, and
    ``next_code`` tracks the host ``TOFECPolicy`` fed the same backlog."""
    arch, params = _model(0)
    truth = _prompts(arch, 12, 0)
    policy = TOFECPolicy.for_classes([CLS], L)
    with closed_loop(arch, params, truth, max_seq=24, store=_erasing(0),
                     request_class=CLS, codec=backend) as (srv, keys):
        for size in (3, 5, 2, 12):
            res = srv.serve_round(keys[:size], steps=1)
            _served_prompts_equal(res, truth, keys)
            # The in-jit controller tracks the host policy's EWMA + thresholds.
            assert res.next_code == policy.select(q=size, idle=0)
        erased = sum(r.failures for r in srv.proxy.results)
    assert erased > 0


def test_fused_step_requires_jitted_backend():
    with pytest.raises(ValueError, match="host-only"):
        FusedServingStep.for_policy(ServePolicy.tofec(), CLS, L, codec=Codec("numpy"))


def test_fused_step_retrace_bounded_across_codes_and_batches():
    """A stream of round sizes and erasure patterns compiles at most once per
    batch bucket: patterns travel as runtime matrices, never as trace
    constants, and a second pass over the stream compiles nothing."""
    arch, params = _model(2)
    truth = _prompts(arch, 8, 2)
    sizes = [2, 3, 8, 5, 4, 7, 6, 2]
    with closed_loop(arch, params, truth, max_seq=24, store=_erasing(2),
                     request_class=CLS) as (srv, keys):
        for size in sizes:
            _served_prompts_equal(srv.serve_round(keys[:size], steps=1), truth, keys)
        traces = srv.traces
        for size in sizes:
            _served_prompts_equal(srv.serve_round(keys[:size], steps=1), truth, keys)
        erased = sum(r.failures for r in srv.proxy.results)
    buckets = {pow2_bucket(size) for size in sizes}
    assert erased > 0
    assert traces <= len(buckets), f"{traces} fused compilations for {len(buckets)} buckets"
    assert srv.traces == traces  # the second pass is compile-free


def test_fused_ewma_state_threads_across_calls():
    """q_ewma persists on device between rounds: heavy rounds walk the
    controller down to (1, 1), and the light round after them still
    remembers them, like the host policy."""
    arch, params = _model(3)
    policy = TOFECPolicy.for_classes([CLS], L)
    with closed_loop(arch, params, _prompts(arch, 16, 3), max_seq=24,
                     request_class=CLS) as (srv, keys):
        codes_fused, codes_host = [], []
        for size in [1, 1, 16, 16, 16, 1, 1, 1]:
            codes_fused.append(srv.serve_round(keys[:size], steps=1).next_code)
            codes_host.append(policy.select(q=size, idle=0))
        assert codes_fused == codes_host
        assert codes_fused[1] == (5, 3) and codes_fused[4] == (1, 1)
        assert codes_fused[5] != codes_fused[1]  # the EWMA carried the backlog
        srv.step.reset()
        assert srv.serve_round(keys[:1], steps=1).next_code == (5, 3)


def test_fused_step_error_names_env_var(monkeypatch):
    """The numpy-backend error fires at CONSTRUCTION (not trace time) and
    tells the user exactly which knob to turn."""
    monkeypatch.setenv("REPRO_CODEC_BACKEND", "numpy")
    with pytest.raises(ValueError, match="REPRO_CODEC_BACKEND=jnp") as ei:
        FusedServingStep.for_policy(ServePolicy.tofec(), CLS, L, codec=Codec("numpy"))
    assert "'numpy'" in str(ei.value)  # names the current setting


MPC_GRID = [
    # (cls, L, lam, seed) — ≥4 pinned points spanning pool sizes, classes,
    # and light/heavy arrival rates (cold→warm rate estimator transitions).
    (RequestClass("r3", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12), 16, 2.0, 0),
    (RequestClass("r3", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12), 16, 30.0, 1),
    (RequestClass("w3", 3.0, PAPER_WRITE_3MB, k_max=4, r_max=3.0, n_max=12), 8, 5.0, 2),
    (RequestClass("r1", 1.0, PAPER_READ_3MB, k_max=3, r_max=2.0, n_max=6), 4, 60.0, 3),
]


@pytest.mark.parametrize("cls_,pool,lam,seed", MPC_GRID)
def test_mpc_host_device_parity_draw_for_draw(cls_, pool, lam, seed):
    """On-device MPC (mpc_step_jax) matches the host MPCPolicy decision
    sequence draw-for-draw: same EWMA carries, same k-major first-minimum
    argmin tie-breaking (see the mpc_step_jax docstring for the contract).

    Host timestamps are float64 sums of float32 interarrivals, so the host's
    ``now - last`` reproduces the exact float32 dt the device sees.
    """
    pol = MPCPolicy(cls_, pool)
    tables = MPCTables.from_policy(pol)
    rng = np.random.default_rng(seed)
    dts = rng.exponential(1.0 / lam, 120).astype(np.float32)
    qs = rng.integers(0, 50, 120)
    carry = (jnp.float32(-1.0), jnp.float32(0.0), jnp.float32(0.0))
    now = 0.0
    for i, (dt, q) in enumerate(zip(dts, qs)):
        if i > 0:
            now += float(dt)
        host = pol.select(q=int(q), idle=0, now=now)
        carry, n, k = mpc_step_jax(
            carry, jnp.float32(q), jnp.float32(dt if i > 0 else -1.0), tables
        )
        assert (int(n), int(k)) == host, f"diverged at arrival {i}"
    # the carries themselves stayed bit-identical, not just the decisions
    assert float(carry[0]) == float(pol.q_ewma)
    assert float(carry[1]) == float(pol.mean_ia)


class _Clock:
    """Stands in for the ``time`` module in the engine: the round's clock."""

    def __init__(self):
        self.now = 0.0

    def monotonic(self):
        return self.now


def test_serve_policy_swap_shares_one_trace(monkeypatch):
    """MPC, TOFEC, static and fixed-k run through the SAME fused launch:
    policies are runtime data (ServeTables), so swapping them mid-stream
    never recompiles — and each lane still matches its host policy."""
    policies = {
        "mpc": (ServePolicy.mpc(), MPCPolicy(CLS, L)),
        "tofec": (ServePolicy.tofec(), TOFECPolicy.for_classes([CLS], L)),
        "static": (ServePolicy.static(8, 4), StaticPolicy(8, 4)),
        "fixedk": (ServePolicy.fixedk(4), FixedKAdaptivePolicy(CLS, L, 4)),
    }
    clock = _Clock()
    monkeypatch.setattr(engine_mod, "time", clock)
    arch, params = _model(5)
    truth = _prompts(arch, 8, 5)
    with closed_loop(arch, params, truth, max_seq=24, request_class=CLS) as (srv, keys):
        # The server is fresh for the MPC lane, so its first round has no
        # interarrival (dt < 0), as the reset host policy has none.
        for name, (spec, host_pol) in policies.items():
            srv.step.set_policy(spec.tables(CLS, L))
            srv.step.reset()
            host_pol.reset()
            for i, size in enumerate([5, 7, 8, 6]):  # one batch bucket
                clock.now += 0.05
                res = srv.serve_round(keys[:size], steps=1)
                _served_prompts_equal(res, truth, keys)
                want = host_pol.select(q=size, idle=0, now=clock.now)
                # fixed-k host may propose n beyond this layout; the fused step
                # reports the controller's raw pick, same as the host policy.
                assert res.next_code == want, (name, i)
    assert srv.traces == 1, f"policy swap retraced: {srv.traces} compiles"


@pytest.mark.parametrize("kind", ["tofec", "static", "fixedk", "mpc"])
def test_every_policy_kind_gives_tables_of_one_shape(kind):
    """Each kind resolves to the tofec tables' shapes and dtypes, so
    ``set_policy`` to it keeps the fused launch's trace."""
    spec = {"tofec": ServePolicy.tofec(), "static": ServePolicy.static(8, 4),
            "fixedk": ServePolicy.fixedk(4), "mpc": ServePolicy.mpc()}[kind]
    step = FusedServingStep.for_policy(spec, CLS, L, codec=Codec("jnp"))
    ref = ServePolicy.tofec().tables(CLS, L)
    shape = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    assert jax.tree.structure(step.tables) == jax.tree.structure(ref)
    assert shape(step.tables) == shape(ref)
    assert step.tables.h_k.shape == (CLS.k_max + 1,)
    assert step.tables.h_n.shape == (CLS.n_max + 1,)


def test_closed_loop_round_is_one_launch_and_feeds_writes():
    """ONE jitted step per round covers admission → batched decode →
    bytes→tokens → prefill (trace count bounded per shape bucket), generated
    tokens match plain generation, and the controller's pick lands in the
    proxy's write policy each round."""
    arch, params = _model(2)
    truth = _prompts(arch, 4, 6)
    write_pol = FeedbackPolicy(8, 4)
    with closed_loop(arch, params, truth, max_seq=64, write_policy=write_pol,
                     request_class=CLS) as (server, keys):
        results = [server.serve_round(keys, steps=3) for _ in range(4)]
        # one shape bucket (fixed batch/layout) → exactly one fused compile
        assert server.traces == 1, f"{server.traces} compiles for 4 rounds"
        for res in results:
            assert res.ok == [True] * 4
            assert res.next_code == write_pol.code  # loop is closed
        # same tokens as prefill+decode on the ground-truth prompts
        np.testing.assert_array_equal(results[-1].tokens,
                                      greedy_reference(arch, params, truth, 3, max_seq=64))
        # and the fed-back code governs the next queued write end-to-end
        proxy, layout = server.proxy, server.layout
        rng = np.random.default_rng(6)
        payload = rng.integers(0, 256, layout.file_bytes, dtype=np.uint8).tobytes()
        proxy.write_async("w/0", layout, payload)
        proxy.flush_writes()
        wres = [r for r in proxy.results if r.op == "write"]
        assert wres and (wres[-1].n, wres[-1].k) == write_pol.code
        back = proxy.read("w/0", layout, payload_len=len(payload))
        assert back.ok and back.data == payload


def test_jitted_steps_keep_module_names_under_named_scopes():
    """The device trace keys on the XLA module names ``jit_core`` (the fused
    launch) and ``jit_decode_step``; their ops carry the named scopes."""
    from repro.serve.engine import DECODE_SCOPE, FUSED_SCOPE, split_cache

    arch = get("qwen1.5-0.5b", smoke=True)
    params = arch.init(jax.random.key(0))
    engine = ServingEngine(arch, params, max_seq=32)
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=16)
    codec = Codec("jnp")
    step = FusedServingStep.for_policy(ServePolicy.tofec(), CLS, L, codec=codec)
    proxy = Proxy(MemoryStore(), StaticPolicy(8, 4), L=4)
    try:
        srv = ClosedLoopServer(engine, proxy, layout, step, prompt_len=16)
        present = np.tile(np.arange(4, 8), (2, 1))
        mats = codec.decode_mats(present, layout.N, layout.K)
        rows = np.zeros((2, layout.K, layout.strip_bytes), np.uint8)
        mats_p, rows_p, bkey = codec.pad_to_bucket("dec", mats, rows, layout.N, layout.K)
        fused = srv._fn(("pfd", *bkey, 16, layout.strip_bytes)).lower(
            step.tables, step.carry, codec.backend.prep_mats(mats_p), rows_p,
            np.float32(2), np.float32(-1.0), params)
    finally:
        proxy.close()
    decode = engine._decode_step.lower(params, jnp.zeros((2, 1), jnp.int32),
                                       *split_cache(arch.init_cache(2, 32)))
    for lowered, fn, scope in [(fused, "core", FUSED_SCOPE),
                               (decode, "decode_step", DECODE_SCOPE)]:
        assert lowered.as_text().startswith(f"module @jit_{fn} ")
        assert f'op_name="jit({fn})/{scope}/' in lowered.compile().as_text()
