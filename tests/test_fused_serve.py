"""Fused jitted serving step: one launch = TOFEC admission update + batched
codec work. Correctness vs the host oracle/policy, bounded retracing across
heterogeneous codes and batch sizes, and the engine's batched fetch path."""

import numpy as np
import pytest

import jax

from repro.coding import rs
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
from repro.storage import MemoryStore, Proxy

CLS = RequestClass("read3mb", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12)
L = 16
JIT_BACKENDS = ["jnp", "pallas"]


def _erased(rng, data, n, k):
    batch = data.shape[0]
    coded = np.stack([rs.encode(data[i], n, k) for i in range(batch)])
    present = np.stack([rng.permutation(n)[:k] for _ in range(batch)])
    rows = np.stack([coded[i][present[i]] for i in range(batch)])
    return coded, present, rows


@pytest.mark.parametrize("backend", JIT_BACKENDS)
def test_fused_decode_matches_oracle_and_policy(backend):
    step = FusedServingStep.for_class(CLS, L, codec=Codec(backend))
    policy = TOFECPolicy.for_classes([CLS], L)
    rng = np.random.default_rng(0)
    n, k = 12, 6
    for q, batch, B in [(0, 3, 100), (4, 5, 57), (30, 2, 128)]:
        data = rng.integers(0, 256, size=(batch, k, B), dtype=np.uint8)
        _, present, rows = _erased(rng, data, n, k)
        got, next_code = step.decode_batch(rows, present, n=n, k=k, q=q)
        np.testing.assert_array_equal(got, data)
        # The in-jit controller tracks the host policy's EWMA + thresholds.
        assert next_code == policy.select(q=q, idle=0)


@pytest.mark.parametrize("backend", JIT_BACKENDS)
def test_fused_encode_matches_oracle(backend):
    step = FusedServingStep.for_class(CLS, L, codec=Codec(backend))
    rng = np.random.default_rng(1)
    for n, k, batch, B in [(12, 6, 4, 64), (5, 3, 2, 200), (3, 3, 2, 40)]:
        data = rng.integers(0, 256, size=(batch, k, B), dtype=np.uint8)
        coded, next_code = step.encode_batch(data, n=n, k=k, q=1.0)
        want = np.stack([rs.encode(data[i], n, k) for i in range(batch)])
        np.testing.assert_array_equal(coded, want)
        assert next_code[0] >= next_code[1] >= 1


def test_fused_step_requires_jitted_backend():
    with pytest.raises(ValueError, match="host-only"):
        FusedServingStep.for_class(CLS, L, codec=Codec("numpy"))


def test_fused_step_retrace_bounded_across_codes_and_batches():
    """A heterogeneous stream of (n, k) codes, erasure patterns and batch
    sizes compiles at most once per shape bucket: codes + patterns travel as
    runtime matrices, never as trace constants."""
    step = FusedServingStep.for_class(CLS, L, codec=Codec("jnp"))
    rng = np.random.default_rng(2)
    stream = [
        (n, k, batch, Bw)
        for k in (2, 4)
        for n in (k, k + 1, 2 * k)
        for batch in (1, 3, 8)
        for Bw in (33, 120)
    ]
    buckets = set()
    calls = 0
    for n, k, batch, Bw in stream * 2:  # second pass must be compile-free
        data = rng.integers(0, 256, size=(batch, k, Bw), dtype=np.uint8)
        _, present, rows = _erased(rng, data, n, k)
        got, _ = step.decode_batch(rows, present, n=n, k=k, q=float(batch))
        np.testing.assert_array_equal(got, data)
        calls += 1
        buckets.add(("dec", k, pow2_bucket(k), pow2_bucket(Bw, Codec.B_FLOOR),
                     pow2_bucket(batch)))
        if n > k:
            coded, _ = step.encode_batch(data, n=n, k=k, q=float(batch))
            calls += 1
            buckets.add(("enc", k, pow2_bucket(n - k), pow2_bucket(Bw, Codec.B_FLOOR),
                         pow2_bucket(batch)))
    assert step.traces <= len(buckets), (
        f"{step.traces} fused compilations for {len(buckets)} shape buckets"
    )
    assert calls > 2 * len(buckets)  # sanity: far fewer compiles than calls


def test_fused_ewma_state_threads_across_calls():
    """q_ewma persists on device between rounds: repeated heavy-q rounds walk
    the controller from max chunking down to (1, 1), like the host policy."""
    step = FusedServingStep.for_class(CLS, L, codec=Codec("jnp"))
    policy = TOFECPolicy.for_classes([CLS], L)
    rng = np.random.default_rng(3)
    n, k = 12, 6
    data = rng.integers(0, 256, size=(2, k, 64), dtype=np.uint8)
    _, present, rows = _erased(rng, data, n, k)
    codes_fused, codes_host = [], []
    for q in [0, 0, 40, 40, 40, 0, 0, 0]:
        _, nxt = step.decode_batch(rows, present, n=n, k=k, q=q)
        codes_fused.append(nxt)
        codes_host.append(policy.select(q=q, idle=0))
    assert codes_fused == codes_host
    assert codes_fused[1] == (12, 6) and codes_fused[4] == (1, 1)
    step.reset()
    _, nxt = step.decode_batch(rows, present, n=n, k=k, q=0)
    assert nxt == (12, 6)


def test_engine_fused_fetch_matches_unfused_end_to_end():
    arch = get("qwen1.5-0.5b", smoke=True)
    params = arch.init(jax.random.key(1))
    eng = ServingEngine(arch, params, max_seq=64)

    prompt_len = 16
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=prompt_len)
    store = MemoryStore()
    rng = np.random.default_rng(4)
    keys, truth = [], []
    for i in range(4):
        toks = rng.integers(0, arch.cfg.vocab, size=(prompt_len,)).astype(np.int32)
        key = f"prompt/{i}"
        ServingEngine.store_prompt(store, key, layout, toks)
        keys.append(key)
        truth.append(toks)

    cls = RequestClass("prompt", prompt_len * 4 / 2**20, PAPER_READ_3MB,
                       k_max=4, r_max=2.0, n_max=8)
    fused = FusedServingStep.for_class(cls, L=8, codec=Codec("jnp"))
    proxy = Proxy(store, StaticPolicy(4, 2), L=8)
    try:
        res = eng.serve(proxy, layout, keys, prompt_len=prompt_len, steps=4)
        fres = eng.serve(proxy, layout, keys, prompt_len=prompt_len, steps=4,
                         fused=fused)
        assert res.next_code is None and fres.next_code is not None
        np.testing.assert_array_equal(fres.tokens, res.tokens)
        direct = eng.generate(np.stack(truth), steps=4)
        np.testing.assert_array_equal(fres.tokens, direct)
        assert all(c == (4, 2) for c in fres.codes)
    finally:
        proxy.close()


def test_fused_step_error_names_env_var(monkeypatch):
    """The numpy-backend error fires at CONSTRUCTION (not trace time) and
    tells the user exactly which knob to turn."""
    monkeypatch.setenv("REPRO_CODEC_BACKEND", "numpy")
    with pytest.raises(ValueError, match="REPRO_CODEC_BACKEND=jnp") as ei:
        FusedServingStep.for_class(CLS, L, codec=Codec("numpy"))
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


def test_serve_policy_swap_shares_one_trace():
    """MPC, TOFEC, static and fixed-k run through the SAME fused launch:
    policies are runtime data (ServeTables), so swapping them mid-stream
    never recompiles — and each lane still matches its host policy."""
    policies = {
        "tofec": (ServePolicy.tofec(), TOFECPolicy.for_classes([CLS], L)),
        "static": (ServePolicy.static(8, 4), StaticPolicy(8, 4)),
        "fixedk": (ServePolicy.fixedk(4), FixedKAdaptivePolicy(CLS, L, 4)),
        "mpc": (ServePolicy.mpc(), MPCPolicy(CLS, L)),
    }
    step = FusedServingStep.for_policy(policies["tofec"][0], CLS, L,
                                       codec=Codec("jnp"))
    rng = np.random.default_rng(5)
    n, k = 12, 6
    data = rng.integers(0, 256, size=(2, k, 64), dtype=np.uint8)
    _, present, rows = _erased(rng, data, n, k)
    for name, (spec, host_pol) in policies.items():
        step.set_policy(spec.tables(CLS, L))
        step.reset()
        host_pol.reset()
        now = 0.0
        for i, q in enumerate([0, 7, 25, 3]):
            now += 0.05
            got, nxt = step.decode_batch(rows, present, n=n, k=k, q=q,
                                         dt=(0.05 if i > 0 else -1.0))
            np.testing.assert_array_equal(got, data)
            want = host_pol.select(q=q, idle=0, now=now)
            # fixed-k host may propose n beyond this layout; the fused step
            # reports the controller's raw pick, same as the host policy.
            assert nxt == want, (name, i)
    assert step.traces == 1, f"policy swap retraced: {step.traces} compiles"


def test_closed_loop_round_is_one_launch_and_feeds_writes():
    """Tentpole acceptance: ONE jitted step per round covers admission →
    batched decode → bytes→tokens → prefill (trace count bounded per shape
    bucket), generated tokens match the unfused engine, and the controller's
    pick lands in the proxy's write policy each round."""
    arch = get("qwen1.5-0.5b", smoke=True)
    params = arch.init(jax.random.key(2))
    eng = ServingEngine(arch, params, max_seq=64)

    prompt_len = 16
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=prompt_len)
    store = MemoryStore()
    rng = np.random.default_rng(6)
    keys, truth = [], []
    for i in range(4):
        toks = rng.integers(0, arch.cfg.vocab, size=(prompt_len,)).astype(np.int32)
        ServingEngine.store_prompt(store, f"p/{i}", layout, toks)
        keys.append(f"p/{i}")
        truth.append(toks)

    write_pol = FeedbackPolicy(layout.N, layout.K)
    proxy = Proxy(store, StaticPolicy(8, 4), L=8, write_policy=write_pol)
    step = FusedServingStep.for_policy(ServePolicy.tofec(), CLS, L,
                                       codec=Codec("jnp"))
    server = ClosedLoopServer(eng, proxy, layout, step, prompt_len=prompt_len)
    try:
        results = [server.serve_round(keys, steps=3) for _ in range(4)]
        # one shape bucket (fixed batch/layout) → exactly one fused compile
        assert server.traces == 1, f"{server.traces} compiles for 4 rounds"
        for res in results:
            assert res.ok == [True] * 4
            assert res.next_code == write_pol.code  # loop is closed
        # same tokens as prefill+decode on the ground-truth prompts
        direct = eng.generate(np.stack(truth), steps=3)
        np.testing.assert_array_equal(results[-1].tokens, direct)
        # and the fed-back code governs the next queued write end-to-end
        payload = rng.integers(0, 256, layout.file_bytes, dtype=np.uint8).tobytes()
        server.put("w/0", payload)
        proxy.flush_writes()
        wres = [r for r in proxy.results if r.op == "write"]
        assert wres and (wres[-1].n, wres[-1].k) == write_pol.code
        back = proxy.read("w/0", layout, payload_len=len(payload))
        assert back.ok and back.data == payload
    finally:
        proxy.close()


def test_jitted_steps_keep_module_names_under_named_scopes():
    """The device trace keys on the XLA module names ``jit_core`` (the fused
    launch) and ``jit_decode_step``; their ops carry the named scopes."""
    from repro.serve.engine import DECODE_SCOPE, FUSED_SCOPE

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
        fused = srv._fn(("pfd", *bkey, 16, layout.strip_bytes, False)).lower(
            step.tables, step.carry, codec.backend.prep_mats(mats_p), rows_p,
            np.float32(2), np.float32(-1.0), params)
    finally:
        proxy.close()
    decode = engine._decode.lower(params, jnp.zeros((2, 1), jnp.int32),
                                  arch.init_cache(2, 32))
    for lowered, fn, scope in [(fused, "core", FUSED_SCOPE),
                               (decode, "decode_step", DECODE_SCOPE)]:
        assert lowered.as_text().startswith(f"module @jit_{fn} ")
        assert f'op_name="jit({fn})/{scope}/' in lowered.compile().as_text()
