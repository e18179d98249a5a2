"""Pallas TPU kernels: GF(2) matrix multiply (bit-matrix Reed-Solomon encode).

TPU adaptation of the paper's MDS encode/decode hot loop (DESIGN.md §3):
GF(256) arithmetic is lifted to GF(2) by expanding each field constant into
its 8x8 binary multiplication matrix. Encoding k data strips of B bytes with
an (n, k) generator then becomes

    C2[8(n-k), B] = ( G2[8(n-k), 8k] @ D2[8k, B] ) mod 2

where G2 is the expanded parity matrix and D2 the LSB-first bit-planes of
the data. A 0/1 matmul with int accumulation is exactly MXU-shaped; the
mod-2 runs in the epilogue on the VPU.

Two kernels are provided:

* :func:`gf2_matmul` — the classic three-level tiled 0/1 matmul
  (grid = (M/bm, N/bn, K/bk), fp32 VMEM scratch accumulator, bf16 MXU
  operands); callers pack/unpack bit-planes themselves.
* :func:`gf2_rs_matmul_bytes` — the batched, fused codec path: raw uint8
  byte strips in, raw uint8 byte strips out. The bitplane unpack of the
  data tile, the GF(2) matmul against a per-item bit-matrix, and the
  bitplane repack of the result all happen inside one kernel invocation
  (grid = (batch, M/bm, B/bn)), so a batch of codewords is one launch and
  ``bytes_to_bitplanes`` stops being a separate pass over HBM.

Interpret mode follows the backend (:func:`resolve_interpret`): the Pallas
interpreter runs on the CPU only, and the TPU always gets the compiled
Mosaic kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the name both kernels carry into the HLO (the custom call's op name and
#: its ``kernel_name``), so a device trace finds them after a refactor
KERNEL_NAME = "gf2_rs_matmul"


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Interpret mode for the current backend: on for the CPU, off elsewhere.

    ``None`` follows ``jax.default_backend()``. An explicit ``False`` is
    always allowed (compiling for a described TPU from a CPU host); asking
    for the interpreter on an accelerator raises, so a chip run can never
    fall back to it.
    """
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            f"Pallas interpret mode requested on backend {jax.default_backend()!r}; "
            "it is for the CPU only"
        )
    return bool(interpret)


def _gf2mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k_tiles: int):
    """One (bm, bn) output tile; accumulates over the K grid dimension."""

    @pl.when(pl.program_id(2) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.bfloat16)
    b = b_ref[...].astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k_tiles - 1)
    def _epilogue():
        # mod-2 of an exact small-integer float: cast and mask the LSB.
        o_ref[...] = (acc_ref[...].astype(jnp.int32) & 1).astype(o_ref.dtype)


def gf2_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 512,
    block_k: int = 128,
    out_dtype=jnp.uint8,
    interpret: bool | None = None,
) -> jax.Array:
    """(A @ B) mod 2 for 0/1 matrices. A: (M, K), B: (K, N) -> (M, N).

    Inputs may be any integer/float dtype holding 0/1 values. Dimensions are
    padded to tile multiples internally (zero rows/cols contribute nothing).
    """
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad shapes {a.shape} @ {b.shape}")
    M, K = a.shape
    _, N = b.shape
    bm, bn, bk = block_m, block_n, block_k

    Mp, Kp, Np = (-(-M // bm) * bm, -(-K // bk) * bk, -(-N // bn) * bn)
    a_p = jnp.zeros((Mp, Kp), jnp.bfloat16).at[:M, :K].set(a.astype(jnp.bfloat16))
    b_p = jnp.zeros((Kp, Np), jnp.bfloat16).at[:K, :N].set(b.astype(jnp.bfloat16))

    n_k_tiles = Kp // bk
    grid = (Mp // bm, Np // bn, n_k_tiles)

    out = pl.pallas_call(
        functools.partial(_gf2mm_kernel, n_k_tiles=n_k_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
        name=KERNEL_NAME,
    )(a_p, b_p)
    return out[:M, :N]


def _rs_bytes_kernel(a_ref, d_ref, o_ref, *, k: int):
    """Fused tile: unpack byte strips → GF(2) matmul → repack bytes.

    a_ref: (1, bm, 8k) 0/1 bit-matrix rows for this batch item.
    d_ref: (1, k, bn) raw data bytes (the whole contraction dim at once —
           k ≤ 256 so 8k ≤ 2048 columns fit comfortably in VMEM).
    o_ref: (1, bm // 8, bn) raw output bytes.
    """
    # Mosaic has no uint8 -> bf16 cast; widen through int32 and f32.
    a = a_ref[0].astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)  # (bm, 8k)
    d = d_ref[0]  # (k, bn) uint8
    bm = a.shape[0]
    bn = d.shape[1]

    # Unpack LSB-first bitplanes in-register: row 8i+b of planes is bit b of
    # data row i, matching gf256.bytes_to_bitplanes.
    shifts = jax.lax.broadcasted_iota(jnp.int32, (k, 8, bn), dimension=1)
    planes = (d[:, None, :].astype(jnp.int32) >> shifts) & 1
    planes = planes.reshape(8 * k, bn).astype(jnp.bfloat16)

    # 0/1 matmul, exact in bf16 operands / fp32 accumulation (sums ≤ 2048).
    acc = jnp.dot(a, planes, preferred_element_type=jnp.float32)
    bits = acc.astype(jnp.int32) & 1  # (bm, bn) mod-2 epilogue

    # Repack: output byte row i collects plane rows 8i..8i+7.
    oshift = jax.lax.broadcasted_iota(jnp.int32, (bm // 8, 8, bn), dimension=1)
    packed = jnp.sum(bits.reshape(bm // 8, 8, bn) << oshift, axis=1)
    o_ref[0] = packed.astype(o_ref.dtype)


def gf2_rs_matmul_bytes(
    bitmats: jax.Array,
    data: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched fused RS matmul on raw bytes.

    bitmats: (batch, 8m, 8k) 0/1 — per-item GF(2)-expanded coding matrices
             (parity rows for encode, inverted generator rows for decode).
    data:    (batch, k, B) uint8 — raw byte strips.
    Returns  (batch, m, B) uint8: the GF(256) product rows, bytes in / bytes
    out, pack/unpack fused into the kernel (no separate bitplane pass).

    batch, m and B should be pre-bucketed by the caller (repro.coding.codec)
    so heterogeneous (n, k) streams reuse a small set of compilations.
    """
    if bitmats.ndim != 3 or data.ndim != 3:
        raise ValueError(f"bad ranks {bitmats.shape} / {data.shape}")
    batch, M, K8 = bitmats.shape
    _, k, B = data.shape
    if K8 != 8 * k or M % 8 or data.shape[0] != batch:
        raise ValueError(f"inconsistent shapes {bitmats.shape} / {data.shape}")

    bm = min(block_m, M)
    bn = min(block_n, B)
    Mp = -(-M // bm) * bm
    Bp = -(-B // bn) * bn
    if Mp != M:
        bitmats = jnp.concatenate(
            [bitmats, jnp.zeros((batch, Mp - M, K8), bitmats.dtype)], axis=1
        )
    if Bp != B:
        data = jnp.concatenate([data, jnp.zeros((batch, k, Bp - B), data.dtype)], axis=2)

    grid = (batch, Mp // bm, Bp // bn)
    out = pl.pallas_call(
        functools.partial(_rs_bytes_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, K8), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, k, bn), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm // 8, bn), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((batch, Mp // 8, Bp), jnp.uint8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=resolve_interpret(interpret),
        name=KERNEL_NAME,
    )(bitmats, data)
    return out[:, : M // 8, :B]
