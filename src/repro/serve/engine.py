"""Serving a language model from TOFEC-admitted, erasure-coded prompt storage.

One path turns coded prompts into tokens, :meth:`ClosedLoopServer.
serve_round`. A round fetches its prompts through the TOFEC proxy as raw
chunks (erasure-coded ranged reads, the (n, k) of each read picked from the
proxy's backlog), runs ONE jitted launch — the admission update, the batched
MDS decode, bytes → tokens and the LM prefill — then decodes greedily with
the engine's cached decode step, and pushes the controller's (n, k) pick
into the proxy's write policy (:class:`repro.core.controller.
FeedbackPolicy`), so the next admission round's queued writes encode under
the adapted code. This is the paper's §III loop closed end to end; the
storage path is the paper's system, the LM path the substrate it feeds.

The controller is runtime data (:class:`ServeTables`): TOFEC, static,
fixed-k (threshold tables from :func:`repro.core.controller.policy_tables`)
and MPC (the traceable cost-model argmin,
:func:`repro.core.controller.mpc_step_jax`) all run through the same trace,
so swapping the policy swaps arrays and never recompiles.

Compilation is shape-bucketed like :mod:`repro.coding.codec` (powers of two
on batch and strip width), and the per-item decode matrices travel as
runtime arrays built on the host from the cached Cauchy tables, so a stream
of erasure patterns and round sizes reuses one trace per shape bucket
(asserted in ``tests/test_fused_serve.py``).
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
from repro.coding.layout import SharedKeyLayout
from repro.core.controller import (
    FeedbackPolicy,
    MPCTables,
    PolicySpec,
    mpc_step_jax,
    mpc_tables,
    policy_tables,
    tofec_threshold_step,
)
from repro.core.delay_model import RequestClass
from repro.models.registry import Arch
from repro.storage.proxy import Proxy
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
    array contents, never recompiles. Threshold encodings are
    :func:`repro.core.controller.policy_tables`' (BIG sentinel, inert
    trailing zeros); the MPC lane rides in
    :class:`repro.core.controller.MPCTables`.
    """

    pol: jax.Array  # () int32: POL_THRESH | POL_MPC
    h_k: jax.Array  # (k_max + 1,) float32 thresholds (zeros on the MPC lane)
    h_n: jax.Array  # (n_max + 1,) float32
    r_max: jax.Array  # () float32
    alpha: jax.Array  # () float32 backlog-EWMA memory (threshold lane)
    mpc: MPCTables


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
    """The serving round's controller, which :class:`ClosedLoopServer` runs
    inside its fused launch; it launches nothing itself.

    It holds the jitted codec the launch decodes with, the policy as
    runtime data (:class:`ServeTables`, swapped live by :meth:`set_policy`)
    and the controller carry (q̄ backlog EWMA + the MPC rate pair), which
    lives on the device and is threaded from one round to the next, so a
    round is the serving-path twin of one
    :func:`repro.core.jax_sim.simulate_tofec_scan` iteration.
    """

    def __init__(self, tables: ServeTables, *, codec: codec_mod.Codec | None = None):
        self.codec = codec or codec_mod.get_codec()
        if not self.codec.backend.jitted:
            env = os.environ.get("REPRO_CODEC_BACKEND")
            raise ValueError(
                f"codec backend {self.codec.name!r} is host-only: the fused "
                "serving launch runs admission, decode and prefill in one "
                "jitted launch and needs the jnp or pallas backend. Fix: set "
                "REPRO_CODEC_BACKEND=jnp (or REPRO_CODEC_BACKEND=pallas) in "
                "the environment, or pass codec=get_codec('jnp') explicitly "
                f"(REPRO_CODEC_BACKEND is currently {env!r})."
            )
        self.tables = tables
        self.reset()

    @classmethod
    def for_policy(cls, policy: ServePolicy, request_class, L: int, *,
                   codec: codec_mod.Codec | None = None) -> "FusedServingStep":
        return cls(policy.tables(request_class, L), codec=codec)

    def reset(self) -> None:
        # (q_ewma, mean_ia, has_rate); -1.0 = cold-start sentinel.
        self.carry = (jnp.float32(-1.0), jnp.float32(0.0), jnp.float32(0.0))

    @property
    def q_ewma(self) -> jax.Array:
        return self.carry[0]

    def set_policy(self, tables: ServeTables) -> None:
        """Swap the controller live. Same table shapes → zero recompiles."""
        self.tables = tables


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


class ServingEngine:
    """A model's weights with its jitted prefill and cached decode step."""

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
    """The paper's proxy as a closed loop, one jitted launch per round.

    Each :meth:`serve_round`:

    1. fetches the round's prompts through the proxy as raw chunks
       (``raw=True``); a read that lost more than n − k chunks drops out of
       the round (``ok``) instead of failing it;
    2. runs one jitted launch: admission update (:func:`serve_policy_step`)
       → batched MDS decode → bytes → tokens → LM prefill;
    3. decodes greedily with the engine's cached decode step;
    4. pushes the controller's (n, k) into the proxy's write policy, when
       that is a :class:`repro.core.controller.FeedbackPolicy`.
    """

    def __init__(self, engine: ServingEngine, proxy: Proxy, layout: SharedKeyLayout,
                 step: FusedServingStep, *, prompt_len: int):
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
        self.stats = obs.CompileStats(label="serve.ClosedLoopServer")
        self._fns: dict[tuple, object] = {}
        self._lock = threading.Lock()
        self._last_now: float | None = None

    @property
    def traces(self) -> int:
        """Compilations of the fused launch, one per shape bucket."""
        return self.stats.traces

    def _fn(self, key: tuple):
        """The fused launch of one shape bucket, ``("pfd", *the codec's decode
        bucket, prompt_len, strip_bytes)``: the batch varies within its
        power-of-two bucket, and prefill and decode run at the padded batch."""
        with self._lock:
            fn = self._fns.get(key)
        if fn is not None:
            return fn
        backend = self.step.codec.backend
        arch = self.engine.arch
        max_seq = self.engine.max_seq
        K, b, plen = self.layout.K, self.layout.strip_bytes, self.prompt_len
        vocab = arch.cfg.vocab

        # The XLA module is named after this function (``jit_core``).
        def core(tables, carry, mats, rows, q, dt, params):
            self.stats.traces += 1  # runs at trace time only
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

        fn = jax.jit(core)
        with self._lock:
            fn = self._fns.setdefault(key, fn)
        return fn

    def serve_round(self, keys: list[str], *, steps: int) -> ClosedLoopResult:
        """One closed-loop serving round over ``keys``; see the class docstring."""
        with obs.span("serve.round", keys=len(keys), steps=steps):
            return self._serve_round(keys, steps)

    def _serve_round(self, keys: list[str], steps: int) -> ClosedLoopResult:
        with obs.span("serve.fetch", keys=len(keys)):
            results = self.proxy.read_many(keys, self.layout, self.prompt_len * 4,
                                           raw=True)
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
        codec = self.step.codec
        n, k = self.layout.N, self.layout.K
        mats = codec.decode_mats(np.asarray(present, np.int64), n, k)
        mats_p, rows_p, bkey = codec.pad_to_bucket("dec", mats, rows, n, k)
        key = ("pfd", *bkey, self.prompt_len, self.layout.strip_bytes)
        fn = self._fn(key)
        with obs.span("serve.launch", bucket=str(key), batch=len(good)):
            # The backlog signal is the round's size: every key is submitted
            # at once, so the proxy sees the whole round queued.
            carry, n_nxt, k_nxt, toks, logits, cache = fn(
                self.step.tables, self.step.carry,
                jnp.asarray(codec.backend.prep_mats(mats_p)), jnp.asarray(rows_p),
                jnp.float32(len(keys)), jnp.float32(dt), self.engine.params,
            )
        first_logits = logits
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
        if isinstance(self.proxy.write_policy, FeedbackPolicy):
            self.proxy.write_policy.push(*next_code)  # close the write loop
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
