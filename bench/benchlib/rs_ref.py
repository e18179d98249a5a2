"""Plain reference of the stored code: systematic Cauchy Reed-Solomon over
GF(2^8), written from its definition and importing nothing of the program.

The field is GF(2)[x] / (x^8 + x^4 + x^3 + x^2 + 1) (0x11d, generator 2). An
(n, k) codeword of k data strips is the k strips themselves followed by
n - k parity strips; parity i is the GF(256) sum over data strips j of
data_j times 1 / (X_i + Y_j), with X_i = i and Y_j = (n - k) + j. One stored
(N, K) codeword serves every chunk-level code of the layout, and a write of
an adapted code stores a prefix of it.
"""

from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D


@functools.cache
def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, np.int64)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    return exp, log


def mul(a: int, b: int) -> int:
    exp, log = _tables()
    return 0 if a == 0 or b == 0 else int(exp[log[a] + log[b]])


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(256)")
    exp, log = _tables()
    return int(exp[255 - log[a]])


@functools.cache
def _times(c: int) -> np.ndarray:
    """The 256-entry table of x -> c * x."""
    return np.array([mul(c, x) for x in range(256)], np.uint8)


def parity_matrix(n: int, k: int) -> list[list[int]]:
    return [[inv(i ^ ((n - k) + j)) for j in range(k)] for i in range(n - k)]


def encode(data: np.ndarray, n: int, k: int) -> np.ndarray:
    """(k, B) data strips -> (n, B) coded strips."""
    data = np.asarray(data, np.uint8)
    if data.shape[0] != k or not 0 < k <= n <= 256:
        raise ValueError(f"bad encode: data {data.shape}, (n, k) = ({n}, {k})")
    out = np.zeros((n, data.shape[1]), np.uint8)
    out[:k] = data
    for i, row in enumerate(parity_matrix(n, k)):
        for j, c in enumerate(row):
            out[k + i] ^= _times(c)[data[j]]
    return out
