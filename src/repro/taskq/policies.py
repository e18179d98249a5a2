"""Traceable code-selection policies for the task-level engine.

The engine observes the *exact* proxy state at each arrival — the FIFO
backlog length ``q`` and the idle-thread count ``idle`` — so policies here
see what :class:`repro.core.controller.Policy` implementations see on the
host, not the fluid waiting-work proxy of :mod:`repro.core.jax_sim`. Two
policy families ride every grid point as runtime data and are selected with
``jnp.where`` on a per-point id (the fleet's policies-as-data trick), so a
heterogeneous mix of threshold and greedy points compiles once:

* ``POL_TABLE`` — the threshold form ``1 + #{h > q̄}`` shared with the fleet
  (:func:`repro.core.controller.tofec_threshold_step`), covering TOFEC,
  static codes and fixed-k via :func:`repro.core.controller.policy_tables`.
* ``POL_GREEDY`` — §V-A's Greedy heuristic, previously exiled to the host
  event simulator because it needs the instantaneous idle-thread count the
  fluid scan cannot provide. :func:`greedy_select` is its traceable form,
  pinned select-for-select against :class:`repro.core.controller.
  GreedyPolicy` in ``tests/test_taskq.py``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.controller import PolicySpec, policy_tables

#: Per-grid-point policy ids (runtime data, never a static arg).
POL_TABLE = 0
POL_GREEDY = 1


def greedy_select(q, idle, k_max, r_max) -> tuple[jax.Array, jax.Array]:
    """Traceable §V-A Greedy: (n, k) from the idle-thread count.

    Chunk as much as idle threads allow (k = min(k_max, idle)), then add
    redundancy as long as idle threads remain (n = min(⌊r_max·k⌋, idle)) —
    the closed-form argmin of expected completion time over the feasible
    codes when every chosen task can start immediately: more chunks shrink
    each task linearly while redundancy only trims the order-statistic tail,
    so filling idle threads with chunks first is optimal under the paper's
    Δ(B), 1/μ(B) model. Falls back to the basic (1, 1) code when no thread
    is idle. ``q`` is accepted (and ignored) to mirror the host
    :meth:`Policy.select` observation signature; every argument may be a
    tracer. Matches :class:`repro.core.controller.GreedyPolicy` decision for
    decision, including the float-truncation of ``int(r_max · k)``.
    """
    del q  # greedy keys on idle threads only (host parity)
    idle = jnp.asarray(idle, jnp.int32)
    k = jnp.minimum(jnp.asarray(k_max, jnp.int32), idle)
    n = jnp.minimum(
        (jnp.asarray(r_max, jnp.float32) * k.astype(jnp.float32)).astype(jnp.int32),
        jnp.maximum(idle, 1),
    )
    n = jnp.maximum(n, k)
    one = jnp.int32(1)
    return jnp.where(idle > 0, n, one), jnp.where(idle > 0, k, one)


@dataclasses.dataclass(frozen=True)
class EncodedPolicy:
    """One grid point's policy as runtime arrays (tables zeroed for greedy —
    trailing-zero thresholds are inert, the fleet padding convention)."""

    pol: int          # POL_TABLE | POL_GREEDY
    h_k: np.ndarray   # (hk_len,) float32
    h_n: np.ndarray   # (hn_len,) float32
    r_max: float
    alpha: float
    gk_max: int       # greedy k_max (1 for table policies; inert)


def encode_policy(spec: PolicySpec, cls, L: int, hk_len: int, hn_len: int,
                  plan=None) -> EncodedPolicy:
    """Resolve a :class:`repro.core.controller.PolicySpec` for the task engine."""
    h_k = np.zeros(hk_len, np.float32)
    h_n = np.zeros(hn_len, np.float32)
    if spec.kind == "greedy":
        return EncodedPolicy(
            pol=POL_GREEDY, h_k=h_k, h_n=h_n, r_max=float(cls.r_max),
            alpha=spec.alpha, gk_max=int(cls.k_max),
        )
    hk, hn, r_max = policy_tables(spec, cls, L, plan)
    h_k[: len(hk)] = hk
    h_n[: len(hn)] = hn
    return EncodedPolicy(
        pol=POL_TABLE, h_k=h_k, h_n=h_n, r_max=float(r_max),
        alpha=spec.alpha, gk_max=1,
    )
