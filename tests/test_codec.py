"""Unified batched codec engine: cross-backend equivalence, batched-vs-
looped parity, per-item erasure patterns, blob helpers, and the bucketed-jit
retrace guarantee (≤ #buckets compilations for a mixed (n, k) stream)."""

import numpy as np
import pytest

from repro.coding import rs
from repro.coding.codec import Codec, available_backends, get_codec

BACKENDS = ["numpy", "jnp", "pallas"]

# (n, k) grid including the degenerate corners: n = k (no parity) and k = 1
# (replication-style codes).
NK_GRID = [(1, 1), (2, 1), (4, 1), (3, 3), (4, 3), (6, 3), (12, 6), (5, 4), (8, 4)]


def _rng(seed=0):
    return np.random.default_rng(seed)


def test_registry_lists_all_backends():
    assert set(BACKENDS) <= set(available_backends())
    with pytest.raises(ValueError):
        Codec("no-such-backend")


@pytest.mark.parametrize("backend", BACKENDS)
def test_encode_matches_numpy_oracle_over_grid(backend):
    rng = _rng(1)
    c = Codec(backend)
    for n, k in NK_GRID:
        B = int(rng.integers(1, 150))
        batch = int(rng.integers(1, 5))
        data = rng.integers(0, 256, size=(batch, k, B), dtype=np.uint8)
        got = np.asarray(c.encode(data, n, k))
        want = np.stack([rs.encode(data[i], n, k) for i in range(batch)])
        np.testing.assert_array_equal(got, want)
        # systematic prefix
        np.testing.assert_array_equal(got[:, :k], data)


@pytest.mark.parametrize("backend", BACKENDS)
def test_decode_any_k_of_n_per_item_present(backend):
    """One batched decode call across items with different erasure patterns."""
    rng = _rng(2)
    c = Codec(backend)
    for n, k in NK_GRID:
        B = int(rng.integers(1, 100))
        batch = 3
        data = rng.integers(0, 256, size=(batch, k, B), dtype=np.uint8)
        coded = np.stack([rs.encode(data[i], n, k) for i in range(batch)])
        present = np.stack(
            [np.sort(rng.choice(n, size=k, replace=False)) for _ in range(batch)]
        )
        rows = np.stack([coded[i][present[i]] for i in range(batch)])
        got = np.asarray(c.decode(rows, present, n, k))
        np.testing.assert_array_equal(got, data)


@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_equals_looped(backend):
    rng = _rng(3)
    c = Codec(backend)
    n, k, B, batch = 9, 4, 123, 8
    data = rng.integers(0, 256, size=(batch, k, B), dtype=np.uint8)
    batched = np.asarray(c.encode(data, n, k))
    looped = np.stack([np.asarray(c.encode(data[i], n, k)) for i in range(batch)])
    np.testing.assert_array_equal(batched, looped)


@pytest.mark.parametrize("backend", BACKENDS)
def test_single_codeword_rank2_api(backend):
    rng = _rng(4)
    c = Codec(backend)
    data = rng.integers(0, 256, size=(3, 50), dtype=np.uint8)
    coded = np.asarray(c.encode(data, 6, 3))
    assert coded.shape == (6, 50)
    present = (1, 4, 5)
    got = np.asarray(c.decode(coded[list(present)], present, 6, 3))
    np.testing.assert_array_equal(got, data)


@pytest.mark.parametrize("backend", BACKENDS)
def test_blob_helpers_roundtrip_mixed_sizes(backend):
    rng = _rng(5)
    c = Codec(backend)
    n, k = 7, 3
    payloads = [
        rng.integers(0, 256, size=sz, dtype=np.uint8)
        for sz in (1, 17, 1000, 257, 3 * 64)
    ]
    all_strips = c.encode_blobs(payloads, n=n, k=k)
    # batched blob encode must equal the one-at-a-time path byte for byte
    for p, strips in zip(payloads, all_strips):
        np.testing.assert_array_equal(strips, c.encode_blob(p, n=n, k=k))
        assert strips.shape == (n, Codec.strip_bytes(p.size, k))
        present = tuple(np.sort(rng.choice(n, size=k, replace=False)))
        got = c.decode_blob(strips[list(present)], present, n=n, k=k, payload_len=p.size)
        np.testing.assert_array_equal(got, p)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_bucketed_jit_bounds_retraces(backend):
    """A heterogeneous (n, k) stream compiles ≤ once per shape bucket."""
    rng = _rng(6)
    c = Codec(backend)  # fresh instance: clean trace counter + jit cache
    stream = [(n, k) for k in (2, 4) for n in (k, k + 1, k + 2, 2 * k)]
    buckets = set()
    for n, k in stream * 2:  # revisit every code: second pass must be free
        B = int(rng.integers(60, 128))
        data = rng.integers(0, 256, size=(2, k, B), dtype=np.uint8)
        coded = np.asarray(c.encode(data, n, k))
        if n > k:
            buckets.add(c.bucket_key("enc", n, k, B, 2))
        present = tuple(range(n - k, n))
        got = np.asarray(c.decode(coded[:, list(present)], present, n, k))
        np.testing.assert_array_equal(got, data)
        buckets.add(c.bucket_key("dec", n, k, B, 2))
    assert c.stats.traces <= len(buckets), (
        f"{c.stats.traces} compilations for {len(buckets)} shape buckets"
    )
    # sanity: far fewer compilations than calls
    assert c.stats.calls > 2 * len(buckets)


def test_stats_and_numpy_never_compiles():
    c = Codec("numpy")
    data = _rng(7).integers(0, 256, size=(4, 3, 40), dtype=np.uint8)
    c.encode(data, 6, 3)
    assert c.stats.traces == 0
    assert c.stats.calls == 1
    assert c.stats.items == 4


def test_get_codec_is_cached_per_backend():
    a = get_codec("numpy")
    b = get_codec("numpy")
    assert a is b
    assert get_codec("jnp") is not a


def test_encode_rejects_bad_shapes():
    c = Codec("numpy")
    with pytest.raises(ValueError):
        c.encode(np.zeros((2, 4, 8), np.uint8), n=6, k=3)  # k mismatch
    with pytest.raises(ValueError):
        c.encode(np.zeros((3, 8), np.uint8), n=2, k=3)  # n < k
    with pytest.raises(ValueError):
        c.decode(np.zeros((3, 8), np.uint8), (0, 1), n=6, k=3)  # short present


# ---------------------------------------------------------------------------
# Device-derived defaults
# ---------------------------------------------------------------------------


def test_defaults_follow_the_device(monkeypatch):
    """Interpret mode and the default backend come from jax.default_backend():
    the CPU gets the interpreter and the numpy oracle, a TPU the compiled
    kernel, and interpret mode on a TPU is refused. REPRO_CODEC_BACKEND
    stays an explicit override."""
    import jax

    from repro.coding import codec as codec_mod
    from repro.kernels.gf2mm.gf2mm import resolve_interpret

    monkeypatch.delenv("REPRO_CODEC_BACKEND", raising=False)
    assert jax.default_backend() == "cpu"
    assert resolve_interpret(None) is True and resolve_interpret(False) is False
    assert codec_mod.default_backend() == "numpy"
    assert get_codec("pallas").backend.interpret is True

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    with pytest.raises(ValueError, match="interpret"):
        resolve_interpret(True)
    with pytest.raises(ValueError, match="interpret"):
        get_codec("pallas", interpret=True)
    assert codec_mod.default_backend() == "pallas"
    monkeypatch.setenv("REPRO_CODEC_BACKEND", "jnp")
    assert codec_mod.default_backend() == "jnp"


def test_compile_cache_dir_is_fixed_or_the_environment(monkeypatch):
    import jax

    from repro import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env itself
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CACHE_DIR) == jax.config.jax_compilation_cache_dir
        assert compile_cache.CACHE_DIR.parent.joinpath("chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
