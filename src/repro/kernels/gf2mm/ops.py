"""Jit'd user-facing ops over the gf2mm Pallas kernel.

Thin compatibility wrappers around the unified batched codec engine
(:mod:`repro.coding.codec`) pinned to the ``pallas`` backend: this module
used to be one of three divergent encode call-paths (alongside the numpy
oracle in ``rs.py`` and the layout's own path); it now just routes
single-codeword calls through the shared engine, inheriting its shape-
bucketed jit caching and the fused bitplane pack/unpack kernel.

``interpret=None`` (the default) follows the backend: the Pallas
interpreter on the CPU, the compiled kernel on a TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _codec(interpret: bool | None):
    from repro.coding.codec import get_codec

    return get_codec("pallas", interpret=interpret)


def rs_encode(data: jax.Array, *, n: int, k: int, interpret: bool | None = None) -> jax.Array:
    """Systematic RS encode on TPU: (k, B) uint8 -> (n, B) uint8.

    Data rows pass through; parity rows come from the GF(2) bit-matrix
    matmul kernel (batched engine, batch of one).
    """
    if data.shape[0] != k:
        raise ValueError(f"data rows {data.shape[0]} != k {k}")
    return jnp.asarray(_codec(interpret).encode(data, n, k))


def rs_decode(
    rows: jax.Array, *, n: int, k: int, present: tuple[int, ...], interpret: bool | None = None
) -> jax.Array:
    """Reconstruct (k, B) data from k surviving strips via the same kernel.

    ``present`` selects the decode matrix; decode is just encode with the
    inverted generator submatrix (a traced input to the bucketed kernel).
    """
    if rows.shape[0] != k:
        raise ValueError(f"rows {rows.shape[0]} != k {k}")
    present = tuple(int(i) for i in present)
    return jnp.asarray(_codec(interpret).decode(rows, present, n, k))


def encode_blob(payload: np.ndarray, *, n: int, k: int) -> np.ndarray:
    """Host convenience: 1-D uint8 payload -> (n, ceil(len/k)) coded strips."""
    return _codec(None).encode_blob(np.asarray(payload, np.uint8), n=n, k=k)


def decode_blob(
    strips: np.ndarray, present: tuple[int, ...], *, n: int, k: int, payload_len: int
) -> np.ndarray:
    """Host convenience: any k strips (k, strip) + ids -> payload bytes."""
    return _codec(None).decode_blob(
        strips, tuple(int(i) for i in present), n=n, k=k, payload_len=payload_len
    )
