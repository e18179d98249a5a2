"""Batched serving engine with TOFEC-admitted prompt storage.

Flow per request: the prompt blob is fetched from the object store through
the TOFEC proxy (erasure-coded ranged reads, adaptive (n, k) from the proxy
backlog), tokenized prompts are batched, prefilled, and decoded with the
arch's cached ``decode_step``. The storage path is the paper's system; the
LM path is the substrate it feeds.

Three fetch paths:

* **unfused** — :meth:`ServingEngine.fetch_prompts` submits the whole round
  through :meth:`Proxy.read_many`; the proxy's decoder thread decodes the
  completions on the host codec.
* **fused** — pass a :class:`FusedServingStep`: the proxy returns raw chunks
  (``raw=True``) and ONE jitted launch then runs the admission update *and*
  the batched MDS decode for the whole round. The controller is runtime data
  (:class:`ServeTables`): TOFEC, static, fixed-k (threshold form, same
  encodings as the :mod:`repro.fleet` sweeps) and MPC (traceable cost-model
  argmin, :func:`repro.core.controller.mpc_step_jax`) all run through the
  same trace — swapping the policy swaps arrays, never recompiles.
* **closed loop** — :class:`ClosedLoopServer` extends the fused launch with
  the LM prefill: one jitted step covers admission update → batched decode →
  bytes→tokens → prefill, and the controller's (n, k) pick is pushed into
  the proxy's write policy (:class:`repro.core.controller.FeedbackPolicy`)
  so the next admission round's queued writes encode under the adapted code.
  This is the paper's §III loop closed end to end.

Compilation is shape-bucketed exactly like :mod:`repro.coding.codec`
(powers of two on batch / parity rows / strip width), and the per-item
decode matrices travel as *runtime* arrays built host-side from the cached
Cauchy tables — so a heterogeneous stream of codes, erasure patterns and
batch sizes reuses one trace per shape bucket (asserted in
``tests/test_fused_serve.py``).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.coding import codec as codec_mod
from repro.coding import rs
from repro.coding.layout import SharedKeyLayout
from repro.core.controller import (
    FeedbackPolicy,
    MPCTables,
    TofecTables,
    mpc_step_jax,
    mpc_tables,
    tofec_threshold_step,
)
from repro.core.delay_model import RequestClass
from repro.core.static_optimizer import build_class_plan
from repro.models.registry import Arch
from repro.storage.proxy import Proxy, store_coded_object
from repro import obs


#: ServeTables.pol ids: threshold-table controllers (tofec / static / fixedk)
#: vs the MPC cost-model argmin.
POL_THRESH = 0
POL_MPC = 1

#: ``jax.named_scope`` of the fused admission → decode → prefill launch and of
#: one cached decode step: the prefix of their ops' names in the HLO metadata
FUSED_SCOPE = "serve.fused_launch"
DECODE_SCOPE = "serve.decode_step"


class RoutingRecord:
    """Where an MoE model's routed tokens went, per closed-loop round, kept
    for readers after the fact (a benchmark's metric readers), as the
    proxy's ``TELEMETRY`` keeps its requests. Bounded: the oldest rounds go
    first. Dense models record nothing.

    ``rounds`` holds (host time at the round's end, prefill (MoE layers,
    held experts + 1), decode (steps, MoE layers, held experts + 1)) int32:
    the (token, choice) pairs each held expert took, the last column those
    routed to experts held elsewhere, for the fused launch's prefill and for
    each decode step whose token was read back. They are the caches'
    ``expert_tokens``, copied to the host after the round's last token.
    """

    def __init__(self, maxlen: int = 1 << 12):
        self.rounds: deque = deque(maxlen=maxlen)


ROUTING = RoutingRecord()


#: The cache entries a decode step rewrites in place: attention keys and
#: values (a latent model's ``ckv`` and ``kpe``). They are donated to the
#: step, which writes its new position into the same buffers. The rest of a
#: cache is not: a round keeps each step's ``expert_tokens`` past the next step.
DONATED_CACHE = ("k", "v", "ckv", "kpe")


def split_cache(cache: dict) -> tuple[dict, dict]:
    """(the entries donated to a decode step, the rest) of a cache."""
    kv = {n: a for n, a in cache.items() if n in DONATED_CACHE}
    return kv, {n: a for n, a in cache.items() if n not in DONATED_CACHE}


def _expert_tokens(cache):
    """The routing counters an MoE model's step left in its cache, or None."""
    return cache.get("expert_tokens") if isinstance(cache, dict) else None


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ServeTables:
    """The serving controller as pure runtime data (one request class).

    Every field is a device array, so the four policies (TOFEC / static /
    fixed-k in threshold form + MPC) share ONE trace per shape bucket:
    ``pol`` selects the lane inside the step and swapping policies swaps
    array contents, never recompiles. Threshold encodings follow the
    :mod:`repro.fleet` sweep convention (BIG sentinel, inert trailing
    zeros); the MPC lane rides in :class:`repro.core.controller.MPCTables`.
    """

    pol: jax.Array  # () int32: POL_THRESH | POL_MPC
    h_k: jax.Array  # (k_max + 1,) float32 thresholds (zeros on the MPC lane)
    h_n: jax.Array  # (n_max + 1,) float32
    r_max: jax.Array  # () float32
    alpha: jax.Array  # () float32 backlog-EWMA memory (threshold lane)
    mpc: MPCTables

    @classmethod
    def from_tofec(cls, tables: TofecTables, *, alpha: float = 0.99) -> "ServeTables":
        return cls(
            pol=jnp.int32(POL_THRESH),
            h_k=jnp.asarray(tables.h_k, jnp.float32),
            h_n=jnp.asarray(tables.h_n, jnp.float32),
            r_max=jnp.float32(tables.r_max),
            alpha=jnp.float32(alpha),
            mpc=MPCTables.trivial(),
        )


def serve_policy_step(
    carry: tuple[jax.Array, jax.Array, jax.Array],
    q: jax.Array,
    dt: jax.Array,
    tables: ServeTables,
) -> tuple[tuple[jax.Array, jax.Array, jax.Array], jax.Array, jax.Array]:
    """One admission update with the policy as runtime data.

    Carry = (q_ewma, mean_ia, has_rate) float32 scalars, initialized to
    (-1.0, 0.0, 0.0): ``q_ewma < 0`` is the cold-start sentinel (the first
    observation seeds the EWMA) and the rate pair only advances on
    ``dt ≥ 0`` (see :func:`repro.core.controller.mpc_step_jax`). Both lanes
    are evaluated and ``tables.pol`` selects — the price of one small argmin
    buys policy swaps with zero recompiles.
    """
    q_ewma, mean_ia, has_rate = carry
    q = jnp.float32(q)
    dt = jnp.float32(dt)
    q_thr, n_thr, k_thr = tofec_threshold_step(
        q_ewma, q, tables.h_k, tables.h_n, tables.r_max, tables.alpha
    )
    (q_mpc, mean_ia, has_rate), n_mpc, k_mpc = mpc_step_jax(
        (q_ewma, mean_ia, has_rate), q, dt, tables.mpc
    )
    is_mpc = tables.pol == POL_MPC
    carry = (jnp.where(is_mpc, q_mpc, q_thr), mean_ia, has_rate)
    n = jnp.where(is_mpc, n_mpc, n_thr)
    k = jnp.where(is_mpc, k_mpc, k_thr)
    return carry, n, k


@dataclasses.dataclass(frozen=True)
class ServePolicy:
    """Declarative serving controller: tofec | static | fixedk | mpc.

    :meth:`tables` resolves it to :class:`ServeTables` for one request
    class; all four kinds produce identically-shaped tables for the same
    class, so a live policy swap (``FusedServingStep.set_policy``) reuses
    the existing trace.
    """

    kind: str
    n: int = 0
    k: int = 0
    alpha: float = 0.99
    eq7_factor: float = 2.0
    alpha_rate: float = 0.05
    util_cap: float = 0.9
    q_guard: float = 4.0
    alpha_q: float = 0.1

    @classmethod
    def tofec(cls, alpha: float = 0.99, eq7_factor: float = 2.0) -> "ServePolicy":
        return cls("tofec", alpha=alpha, eq7_factor=eq7_factor)

    @classmethod
    def static(cls, n: int, k: int) -> "ServePolicy":
        return cls("static", n=n, k=k)

    @classmethod
    def fixedk(cls, k: int, eq7_factor: float = 2.0) -> "ServePolicy":
        return cls("fixedk", k=k, eq7_factor=eq7_factor)

    @classmethod
    def mpc(cls, *, alpha_rate: float = 0.05, util_cap: float = 0.9,
            q_guard: float = 4.0, alpha_q: float = 0.1) -> "ServePolicy":
        return cls("mpc", alpha_rate=alpha_rate, util_cap=util_cap,
                   q_guard=q_guard, alpha_q=alpha_q)

    def tables(self, request_class: RequestClass, L: int) -> ServeTables:
        # The MPC lane is always populated (shape-stable swaps); threshold
        # kinds just never select it.
        mpc_t = mpc_tables(
            request_class, L, alpha_rate=self.alpha_rate, util_cap=self.util_cap,
            q_guard=self.q_guard, alpha_q=self.alpha_q,
        )
        if self.kind == "mpc":
            h_k = np.zeros(request_class.k_max + 1, np.float32)
            h_n = np.zeros(request_class.n_max + 1, np.float32)
            r_max = request_class.r_max
            pol = POL_MPC
        else:
            from repro.fleet.sweep import PolicySpec, policy_tables

            spec = PolicySpec(self.kind, n=self.n, k=self.k, alpha=self.alpha,
                              eq7_factor=self.eq7_factor)
            h_k, h_n, r_max = policy_tables(spec, request_class, L)
            pol = POL_THRESH
        return ServeTables(
            pol=jnp.int32(pol),
            h_k=jnp.asarray(h_k, jnp.float32),
            h_n=jnp.asarray(h_n, jnp.float32),
            r_max=jnp.float32(r_max),
            alpha=jnp.float32(self.alpha),
            mpc=mpc_t,
        )


class FusedServingStep:
    """One jitted launch per serving round: admission update + batched MDS
    codec work (encode or decode), fused.

    State: the controller carry (q̄ backlog EWMA + the MPC rate pair) lives
    on device and is threaded through successive calls, so the step is the
    serving-path twin of one :func:`repro.core.jax_sim.simulate_tofec_scan`
    iteration. Each call returns the payloads *and* the (n, k) the
    controller picks for the next round.

    Matrices are runtime inputs: decode matrices come from
    :meth:`Codec.decode_mats` (host-cached per erasure pattern), parity
    matrices from the cached Cauchy generator, both padded to the shape
    bucket and run through ``backend.prep_mats``; the controller itself is
    runtime data too (:class:`ServeTables`) — so changing the code, the
    erasure pattern or the *policy* never retraces; only a new shape bucket
    compiles.
    """

    def __init__(self, tables: TofecTables | ServeTables, *,
                 codec: codec_mod.Codec | None = None, alpha: float = 0.99):
        self.codec = codec or codec_mod.get_codec()
        if not self.codec.backend.jitted:
            env = os.environ.get("REPRO_CODEC_BACKEND")
            raise ValueError(
                f"codec backend {self.codec.name!r} is host-only: the fused "
                "serving step runs admission + codec (+ prefill) in one "
                "jitted launch and needs the jnp or pallas backend. Fix: set "
                "REPRO_CODEC_BACKEND=jnp (or REPRO_CODEC_BACKEND=pallas) in "
                "the environment, or pass codec=get_codec('jnp') explicitly "
                f"(REPRO_CODEC_BACKEND is currently {env!r})."
            )
        if isinstance(tables, TofecTables):
            tables = ServeTables.from_tofec(tables, alpha=alpha)
        self.tables = tables
        self.alpha = alpha
        # Outer-jit compilations (bounded by shape buckets); shared
        # CompileStats so retrace accounting is uniform across engines —
        # ``.traces`` stays the public pin via the property below.
        self.stats = obs.CompileStats(label="serve.FusedServingStep")
        self._fns: dict[tuple, object] = {}
        self._lock = threading.Lock()
        self.reset()

    @property
    def traces(self) -> int:
        return self.stats.traces

    @traces.setter
    def traces(self, value: int) -> None:
        self.stats.traces = value

    @classmethod
    def for_class(cls, request_class, L: int, *, codec: codec_mod.Codec | None = None,
                  alpha: float = 0.99, eq7_factor: float = 2.0) -> "FusedServingStep":
        plan = build_class_plan(request_class, L, eq7_factor=eq7_factor)
        return cls(TofecTables.from_plan(plan), codec=codec, alpha=alpha)

    @classmethod
    def for_policy(cls, policy: ServePolicy, request_class, L: int, *,
                   codec: codec_mod.Codec | None = None) -> "FusedServingStep":
        return cls(policy.tables(request_class, L), codec=codec, alpha=policy.alpha)

    def reset(self) -> None:
        # (q_ewma, mean_ia, has_rate); -1.0 = cold-start sentinel.
        self.carry = (jnp.float32(-1.0), jnp.float32(0.0), jnp.float32(0.0))

    @property
    def q_ewma(self) -> jax.Array:
        return self.carry[0]

    def set_policy(self, tables: ServeTables) -> None:
        """Swap the controller live. Same table shapes → zero recompiles."""
        self.tables = tables

    # -- compilation cache ---------------------------------------------------

    def _fn(self, key: tuple):
        with self._lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn
        backend = self.codec.backend
        kind = key[0]

        if kind == "adm":  # admission update only (n == k: no parity work)

            def fused(tables, carry, q, dt):
                self.traces += 1  # runs at trace time only
                return serve_policy_step(carry, q, dt, tables)

        elif kind == "dec":

            def fused(tables, carry, mats, rows, q, dt):
                self.traces += 1  # runs at trace time only
                carry, n_nxt, k_nxt = serve_policy_step(carry, q, dt, tables)
                return carry, n_nxt, k_nxt, backend.matmul_traced(mats, rows)

        else:

            def fused(tables, carry, mats, data, q, dt):
                self.traces += 1  # runs at trace time only
                carry, n_nxt, k_nxt = serve_policy_step(carry, q, dt, tables)
                parity = backend.matmul_traced(mats, data)
                return carry, n_nxt, k_nxt, jnp.concatenate([data, parity], axis=1)

        fn = jax.jit(fused)
        with self._lock:
            fn = self._fns.setdefault(key, fn)
        return fn

    # -- fused entry points ----------------------------------------------------

    def decode_batch(self, rows, present, *, n: int, k: int, q: float,
                     dt: float = -1.0) -> tuple[np.ndarray, tuple[int, int]]:
        """Admission update + batched reconstruct in ONE jitted launch.

        rows: (batch, k, B) surviving strips; present: (batch, k) strip ids
        (or a shared (k,) pattern); q: the round's backlog signal; dt: the
        interarrival seconds feeding the MPC rate estimator (< 0 = unknown;
        threshold policies ignore it). Returns ((batch, k, B) decoded data,
        (n, k) for the next round).
        """
        rows = np.asarray(rows, np.uint8)
        single = rows.ndim == 2
        if single:
            rows = rows[None]
        batch, _, B = rows.shape
        present = np.asarray(present, np.int64)
        if present.ndim == 1:
            present = np.broadcast_to(present, (batch, k))
        mats = self.codec.decode_mats(present, n, k)
        mats_p, rows_p, key = self.codec.pad_to_bucket("dec", mats, rows, n, k)
        fn = self._fn(key)
        with obs.span("serve.decode_batch", bucket=str(key), batch=batch):
            self.carry, n_nxt, k_nxt, out = fn(
                self.tables, self.carry,
                jnp.asarray(self.codec.backend.prep_mats(mats_p)), jnp.asarray(rows_p),
                jnp.float32(q), jnp.float32(dt),
            )
        self.stats.launches += 1
        data = np.asarray(out)[:batch, :k, :B]
        return (data[0] if single else data), (int(n_nxt), int(k_nxt))

    def encode_batch(self, data, *, n: int, k: int, q: float,
                     dt: float = -1.0) -> tuple[np.ndarray, tuple[int, int]]:
        """Admission update + batched systematic encode in ONE launch.

        data: (batch, k, B) → ((batch, n, B) coded strips, next (n, k)).
        """
        data = np.asarray(data, np.uint8)
        single = data.ndim == 2
        if single:
            data = data[None]
        batch, _, B = data.shape
        if n == k:  # no parity: admission update only, data passes through
            fn = self._fn(("adm",))
            self.carry, n_nxt, k_nxt = fn(self.tables, self.carry,
                                          jnp.float32(q), jnp.float32(dt))
            self.stats.launches += 1
            return (data[0] if single else data), (int(n_nxt), int(k_nxt))
        m = n - k
        par = rs.cauchy_parity_matrix(n, k)
        mats = np.broadcast_to(par, (batch, m, k))
        mats_p, data_p, key = self.codec.pad_to_bucket("enc", mats, data, n, k)
        fn = self._fn(key)
        with obs.span("serve.encode_batch", bucket=str(key), batch=batch):
            self.carry, n_nxt, k_nxt, out = fn(
                self.tables, self.carry,
                jnp.asarray(self.codec.backend.prep_mats(mats_p)), jnp.asarray(data_p),
                jnp.float32(q), jnp.float32(dt),
            )
        self.stats.launches += 1
        coded = np.asarray(out)[:batch, :n, :B]
        return (coded[0] if single else coded), (int(n_nxt), int(k_nxt))


def tokens_from_strips(data: jax.Array, k: int, strip_bytes: int,
                       prompt_len: int) -> jax.Array:
    """Traceable bytes→tokens: (batch, ≥k, ≥strip_bytes) decoded uint8 strips
    → (batch, prompt_len) int32, little-endian 4-byte words.

    The slice order matters: padding must come OFF before the flatten
    (slicing after would interleave pad bytes into the token stream).
    """
    flat = data[:, :k, :strip_bytes].reshape(data.shape[0], k * strip_bytes)
    by = flat[:, : prompt_len * 4].reshape(-1, prompt_len, 4).astype(jnp.int32)
    return by[..., 0] | (by[..., 1] << 8) | (by[..., 2] << 16) | (by[..., 3] << 24)


@dataclasses.dataclass
class ServeResult:
    tokens: np.ndarray  # (B, steps) generated ids
    storage_total_s: list[float]  # per-request proxy read delays
    codes: list[tuple[int, int]]  # (n, k) used per prompt fetch
    next_code: tuple[int, int] | None = None  # fused path: controller's pick


class ServingEngine:
    def __init__(self, arch: Arch, params, *, max_seq: int = 128):
        self.arch = arch
        self.params = params
        self.max_seq = max_seq
        self._prefill = jax.jit(
            lambda p, b: arch.prefill(p, b, max_seq=self.max_seq)
        )

        # The XLA module is named after this function (``jit_decode_step``);
        # the scope names its ops in the HLO metadata and the device trace.
        def decode_step(params, token, kv, rest):
            with jax.named_scope(DECODE_SCOPE):
                return arch.decode_step(params, token, {**kv, **rest})

        self._decode_step = jax.jit(decode_step, donate_argnums=(2,))

    def _decode(self, params, token, cache):
        """One cached decode step: (logits, the next cache). The cache's K/V
        (:data:`DONATED_CACHE`) are donated and must not be read again."""
        return self._decode_step(params, token, *split_cache(cache))

    # -- storage integration -------------------------------------------------

    @staticmethod
    def store_prompt(store, key: str, layout: SharedKeyLayout, tokens: np.ndarray):
        store_coded_object(store, key, layout, tokens.astype(np.int32).tobytes())

    def fetch_prompts(
        self, proxy: Proxy, layout: SharedKeyLayout, keys: list[str], prompt_len: int,
        *, fused: FusedServingStep | None = None, retries: int = 3,
    ) -> tuple[np.ndarray, list[float], list[tuple[int, int]], tuple[int, int] | None]:
        """Batched prompt fetch: the whole round is submitted up front (the
        proxy's policy sees it as backlog) and reconstructed — by the proxy's
        decoder thread (unfused) or, batched, by ``fused``'s single jitted
        admission+decode launch (raw chunks in, payloads out).

        Reads that exhaust their n − k failure budget (the backlog-adapted
        code can be as lean as (1, 1)) are resubmitted up to ``retries``
        times; the retry round is smaller, so the policy re-picks with more
        redundancy. Reported delays accumulate across attempts (what the
        client actually waited); codes report the attempt that served."""
        payload_len = prompt_len * 4
        raw = fused is not None
        results = proxy.read_many(keys, layout, payload_len, raw=raw)
        failed_s = [0.0] * len(keys)
        for _ in range(retries):
            bad_idx = [i for i, r in enumerate(results) if not r.ok]
            if not bad_idx:
                break
            for i in bad_idx:
                failed_s[i] += results[i].total_s
            redo = proxy.read_many([keys[i] for i in bad_idx], layout, payload_len,
                                   raw=raw)
            for i, r in zip(bad_idx, redo):
                results[i] = r
        bad = [k for k, r in zip(keys, results) if not r.ok]
        if bad:
            raise RuntimeError(f"prompt fetch failed for {', '.join(bad)}")
        delays = [r.total_s + extra for r, extra in zip(results, failed_s)]
        codes = [(r.n, r.k) for r in results]
        if fused is None:
            toks = [np.frombuffer(r.data, np.int32) for r in results]
            return np.stack(toks), delays, codes, None
        rows, present = layout.gather_rows_batch([(r.k, r.chunks) for r in results])
        data, next_code = fused.decode_batch(
            rows, present, n=layout.N, k=layout.K, q=len(keys)
        )
        toks = [
            np.frombuffer(data[i].reshape(-1)[:payload_len].tobytes(), np.int32)
            for i in range(len(results))
        ]
        return np.stack(toks), delays, codes, next_code

    # -- generation -----------------------------------------------------------

    def generate(self, prompts: np.ndarray, steps: int, *, greedy: bool = True) -> np.ndarray:
        """prompts: (B, S) int32 → (B, steps) generated ids."""
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if self.arch.cfg.family == "vlm":
            B = prompts.shape[0]
            batch["patches"] = jnp.zeros(
                (B, self.arch.cfg.vision_patches, self.arch.cfg.d_model), jnp.float32
            )
        if self.arch.cfg.family == "encdec":
            B = prompts.shape[0]
            batch["frames"] = jnp.zeros(
                (B, self.arch.cfg.encoder_seq, self.arch.cfg.d_model), jnp.float32
            )
        logits, cache = self._prefill(self.params, batch)
        out = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for _ in range(steps):
            out.append(np.asarray(tok)[:, 0])
            logits, cache = self._decode(self.params, tok, cache)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return np.stack(out, axis=1)

    def serve(
        self,
        proxy: Proxy,
        layout: SharedKeyLayout,
        keys: list[str],
        *,
        prompt_len: int,
        steps: int,
        fused: FusedServingStep | None = None,
    ) -> ServeResult:
        prompts, delays, codes, next_code = self.fetch_prompts(
            proxy, layout, keys, prompt_len, fused=fused
        )
        gen = self.generate(prompts, steps)
        return ServeResult(tokens=gen, storage_total_s=delays, codes=codes,
                           next_code=next_code)


@dataclasses.dataclass
class ClosedLoopResult:
    tokens: np.ndarray  # (G, steps) generated ids, one row per SERVED key
    ok: list[bool]  # per input key: did its read survive (per-item mask)
    served_keys: list[str]  # keys in tokens' row order (the ok subset)
    codes: list[tuple[int, int]]  # read (n, k) per served key
    next_code: tuple[int, int]  # controller's pick, pushed to the write policy
    storage_total_s: list[float]  # proxy read delays per served key
    # Device arrays at the padded bucket batch (rows past len(served_keys)
    # are bucket padding), left on device so a round adds no host copy:
    prompts: jax.Array  # (B, prompt_len) int32 decoded prompts
    first_logits: jax.Array  # (B, 1, vocab) f32 prefill logits


class ClosedLoopServer:
    """The paper's proxy as a CLOSED loop, one jitted step per round.

    Each :meth:`serve_round`:

    1. fetches the round's prompts through the proxy (``raw=True`` — chunks
       only, per-item error masks; a partially-failed item drops out of the
       round instead of wedging it),
    2. runs ONE jitted launch: admission update (policy as runtime data,
       :func:`serve_policy_step`) → batched MDS decode → bytes→tokens →
       LM prefill — no per-round host round-trip between those stages,
    3. finishes generation with the engine's cached ``decode_step``,
    4. pushes the controller's (n, k) into the proxy's write policy
       (:class:`repro.core.controller.FeedbackPolicy`), so writes queued for
       the next admission round encode under the adapted code. (The pick is
       read back after generation — which forces the launch anyway — so the
       round never stalls on a mid-round device sync.)

    Trace count is bounded per shape bucket: the cache key is the codec's
    decode bucket extended with (prompt_len, strip_bytes) — the prefill's
    static shape inputs. Batch varies within pow2 buckets; prefill/decode
    run at the padded batch and outputs are sliced on host at the end.
    """

    def __init__(self, engine: ServingEngine, proxy: Proxy, layout: SharedKeyLayout,
                 step: FusedServingStep, *, prompt_len: int,
                 write_policy: FeedbackPolicy | None = None):
        if prompt_len * 4 > layout.file_bytes:
            raise ValueError(
                f"prompt_len {prompt_len} needs {prompt_len * 4} bytes but the "
                f"layout holds {layout.file_bytes}"
            )
        self.engine = engine
        self.proxy = proxy
        self.layout = layout
        self.step = step
        self.prompt_len = prompt_len
        if write_policy is None and isinstance(proxy.write_policy, FeedbackPolicy):
            write_policy = proxy.write_policy
        self.write_policy = write_policy
        self.stats = obs.CompileStats(label="serve.ClosedLoopServer")
        self._fns: dict[tuple, object] = {}
        self._lock = threading.Lock()
        self._last_now: float | None = None
        self._mbuf = None  # device MetricsBuf, created on first collected round
        self._tlbuf = None  # device TimelineBuf ring, same lifecycle as _mbuf
        self._flight = None  # host FlightRing, same lifecycle as _mbuf

    @property
    def traces(self) -> int:
        return self.stats.traces

    @traces.setter
    def traces(self, value: int) -> None:
        self.stats.traces = value

    @property
    def metrics(self):
        """The device-resident :class:`repro.obs.MetricsBuf` accumulated
        across collected rounds (None until a round runs with REPRO_OBS=1).
        Call ``.snapshot()`` on it for plain dicts — the only host sync."""
        return self._mbuf

    @property
    def timeline(self):
        """The device-resident :class:`repro.obs.TimelineBuf` ring of
        per-round samples — arrival rate ``lam``, ``backlog`` signal, the
        controller's ``pick_n``/``pick_k``, ``served`` count, and the
        round's ``delay`` histogram delta (windowed percentiles recoverable
        host-side).  None until a round runs with REPRO_OBS=1; the last
        :data:`_TL_CAP` rounds are retained.  Call ``.snapshot()`` for
        oldest-first numpy series — the only host sync."""
        return self._tlbuf

    @property
    def flight(self):
        """The host-side :class:`repro.obs.flight.FlightRing` of per-round
        phase breakdowns (admit → decode → generate on the compacted
        simulated round clock) — where each round spent its budget.  None
        until a round runs with REPRO_OBS=1; the last :data:`_TL_CAP`
        rounds are retained, matching the timeline ring."""
        return self._flight

    def put(self, key: str, payload: bytes, cls_id: int = 0):
        """Queue a write through the proxy (encodes under the fed-back code
        at the next admission round). Returns the async request handle."""
        return self.proxy.write_async(key, self.layout, payload, cls_id)

    def _fn(self, key: tuple):
        with self._lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn
        backend = self.step.codec.backend
        arch = self.engine.arch
        max_seq = self.engine.max_seq
        K, b, plen = self.layout.K, self.layout.strip_bytes, self.prompt_len
        vocab = arch.cfg.vocab
        collect = key[-1]  # metrics flag is part of the cache key

        def core(tables, carry, mats, rows, q, dt, params):
            self.traces += 1  # runs at trace time only
            with jax.named_scope(FUSED_SCOPE):
                carry, n_nxt, k_nxt = serve_policy_step(carry, q, dt, tables)
                data = backend.matmul_traced(mats, rows)
                toks = tokens_from_strips(data, K, b, plen)
                # Bucket-padding rows decode to zeros; clip keeps any stray
                # bytes inside the embedding table instead of relying on
                # gather clamping.
                toks = jnp.clip(toks, 0, vocab - 1)
                logits, cache = arch.prefill_tokens(params, toks, max_seq=max_seq)
            return carry, n_nxt, k_nxt, toks, logits, cache

        if collect:

            def fused(tables, carry, mats, rows, q, dt, params,
                      mbuf, requested, served, errs, tlbuf, delays):
                carry, n_nxt, k_nxt, toks, logits, cache = core(
                    tables, carry, mats, rows, q, dt, params)
                # Pure additions on the side bufs: the primary outputs'
                # graph is identical to the collect=False trace.
                mbuf = (mbuf.count("serve_rounds", 1)
                            .count("serve_requested", requested)
                            .count("serve_served", served)
                            .count("serve_decode_errors", errs)
                            .observe("serve_q", q)
                            .observe("serve_pick_n", n_nxt)
                            .observe("serve_pick_k", k_nxt)
                            .observe("serve_batch", served)
                            .high("serve_q_hi", q))
                # One timeline ring slot per round.  ``delays`` is padded to
                # the bucket batch (its length is already in the cache key);
                # the lane mask drops the padding from the histogram delta.
                lam = jnp.where(
                    dt > 0,
                    served.astype(jnp.float32) / jnp.maximum(dt, 1e-9),
                    0.0,
                )
                lane = jnp.arange(delays.shape[0])
                wvec = (lane < served).astype(jnp.int32)
                tlbuf = tlbuf.append(
                    {"lam": lam, "backlog": q, "pick_n": n_nxt,
                     "pick_k": k_nxt, "served": served},
                    {"delay": (obs.delay_bucket(delays), wvec)},
                )
                return carry, n_nxt, k_nxt, toks, logits, cache, mbuf, tlbuf

        else:
            fused = core

        fn = jax.jit(fused)
        with self._lock:
            fn = self._fns.setdefault(key, fn)
        return fn

    #: fixed bucket counts for the round histograms (values clip into the
    #: last bucket); one shared buf shape per server, so adding a round
    #: never changes the pytree structure (-> no retrace).
    _Q_BINS = 64

    #: Timeline ring capacity: the last _TL_CAP rounds stay resident;
    #: older slots are overwritten in ring order (snapshot restores
    #: oldest-first).  Capacity is static pytree structure, so it never
    #: varies the trace.
    _TL_CAP = 256

    def _zero_mbuf(self):
        return obs.MetricsBuf.zeros(
            counters=("serve_rounds", "serve_requested", "serve_served",
                      "serve_decode_errors"),
            hists={"serve_q": self._Q_BINS, "serve_batch": self._Q_BINS,
                   "serve_pick_n": obs.PICK_BINS,
                   "serve_pick_k": obs.PICK_BINS},
            highs=("serve_q_hi",),
        )

    def _zero_tlbuf(self):
        return obs.TimelineBuf.zeros(
            self._TL_CAP,
            series=("lam", "backlog", "pick_n", "pick_k", "served"),
            hists={"delay": obs.DELAY_BINS},
        )

    def serve_round(self, keys: list[str], *, steps: int,
                    q: float | None = None) -> ClosedLoopResult:
        """One closed-loop serving round over ``keys``; see class docstring."""
        with obs.span("serve.round", keys=len(keys), steps=steps):
            return self._serve_round(keys, steps=steps, q=q)

    def _serve_round(self, keys: list[str], *, steps: int,
                     q: float | None = None) -> ClosedLoopResult:
        payload_len = self.prompt_len * 4
        collect = obs.enabled()
        t_round0 = time.monotonic()
        with obs.span("serve.fetch", keys=len(keys)):
            results = self.proxy.read_many(keys, self.layout, payload_len,
                                           raw=True)
        t_fetch = time.monotonic()
        ok = [r.ok for r in results]
        good = [r for r in results if r.ok]
        if not good:
            raise RuntimeError(
                f"all {len(keys)} prompt fetches failed this round"
            )
        rows, present = self.layout.gather_rows_batch(
            [(r.k, r.chunks) for r in good]
        )
        now = time.monotonic()
        dt = -1.0 if self._last_now is None else max(now - self._last_now, 1e-9)
        self._last_now = now
        q_sig = float(len(keys)) if q is None else float(q)
        codec = self.step.codec
        n, k = self.layout.N, self.layout.K
        mats = codec.decode_mats(np.asarray(present, np.int64), n, k)
        mats_p, rows_p, bkey = codec.pad_to_bucket("dec", mats, rows, n, k)
        key = ("pfd", *bkey, self.prompt_len, self.layout.strip_bytes, collect)
        fn = self._fn(key)
        args = (
            self.step.tables, self.step.carry,
            jnp.asarray(codec.backend.prep_mats(mats_p)), jnp.asarray(rows_p),
            jnp.float32(q_sig), jnp.float32(dt), self.engine.params,
        )
        with obs.span("serve.launch", bucket=str(key), batch=len(good)):
            if collect:
                if self._mbuf is None:
                    self._mbuf = self._zero_mbuf()
                if self._tlbuf is None:
                    self._tlbuf = self._zero_tlbuf()
                # Host-known round tallies ride as runtime scalars; the
                # error count is the per-item mask's failed-fetch tally.
                # Per-item proxy delays pad to the bucket batch (rows_p's
                # leading axis, already in the cache key).
                delays = np.zeros(rows_p.shape[0], np.float32)
                delays[: len(good)] = [r.total_s for r in good]
                (carry, n_nxt, k_nxt, toks, logits, cache,
                 self._mbuf, self._tlbuf) = fn(
                    *args, self._mbuf, jnp.int32(len(keys)),
                    jnp.int32(len(good)), jnp.int32(len(keys) - len(good)),
                    self._tlbuf, jnp.asarray(delays),
                )
            else:
                carry, n_nxt, k_nxt, toks, logits, cache = fn(*args)
        first_logits = logits
        t_launch = time.monotonic()
        self.stats.launches += 1
        self.step.carry = carry
        # Generation continues at the padded batch (same trace each round);
        # rows are sliced back to the served subset on host at the end.
        gen = []
        routed = [_expert_tokens(cache)]
        with obs.span("serve.generate", steps=steps):
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            for _ in range(steps):
                gen.append(np.asarray(tok)[:, 0])
                logits, cache = self.engine._decode(self.engine.params, tok, cache)
                routed.append(_expert_tokens(cache))
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tokens = np.stack(gen, axis=1)[: len(good)]
        if routed[0] is not None and steps:
            # Every step but the last was read back: its counters are there.
            pre, *dec = jax.device_get(routed[:-1])
            ROUTING.rounds.append((time.monotonic(), pre, np.stack(dec) if dec else
                                   np.zeros((0, *pre.shape), pre.dtype)))
        # Pull the controller's pick to host only now: generation already
        # forced the launch, so this sync is free (reading it before the
        # decode loop would stall the round on the fused launch).
        next_code = (int(n_nxt), int(k_nxt))
        if collect:
            # One flight-ring record per collected round: where the round's
            # budget went.  "decode" covers the whole fused admission +
            # decode + prefill launch (one dispatch — the engine cannot
            # split it host-side); "generate" includes the sync that forces
            # it, which is exactly the wait the client sees.
            from repro.obs.flight import FlightRing

            if self._flight is None:
                self._flight = FlightRing(self._TL_CAP, label="serve")
            self._flight.record(
                [("admit", t_fetch - t_round0),
                 ("decode", t_launch - t_fetch),
                 ("generate", time.monotonic() - t_launch)],
                requested=len(keys), served=len(good), code=next_code,
            )
        if self.write_policy is not None:
            self.write_policy.push(*next_code)  # close the write loop
        return ClosedLoopResult(
            tokens=tokens,
            ok=ok,
            served_keys=[r.key for r in good],
            codes=[(r.n, r.k) for r in good],
            next_code=next_code,
            storage_total_s=[r.total_s for r in good],
            prompts=toks,
            first_logits=first_logits,
        )
