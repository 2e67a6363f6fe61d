"""Plain reference of the jerasure ``reed_sol_van`` code at w=8.

The coding matrix follows jerasure's reed_sol.c: the extended
Vandermonde matrix of (k+m) x k rows, turned by column operations into
a systematic one whose first coding row and first coding column are
all ones (``reed_sol_big_vandermonde_distribution_matrix``).  Parity is
that matrix times the data chunks over GF(2^8), byte by byte.
"""

from __future__ import annotations

import numpy as np

from . import gf256


def extended_vandermonde(rows: int, cols: int) -> np.ndarray:
    v = np.zeros((rows, cols), np.int64)
    v[0, 0] = 1
    if rows == 1:
        return v
    v[rows - 1, cols - 1] = 1
    for i in range(1, rows - 1):
        e = 1
        for j in range(cols):
            v[i, j] = e
            e = gf256.mul(e, i)
    return v


def distribution_matrix(rows: int, cols: int) -> np.ndarray:
    d = extended_vandermonde(rows, cols)
    for i in range(1, cols):
        j = next((r for r in range(i, rows) if d[r, i]), None)
        if j is None:
            raise ValueError("no pivot: rows/cols out of range")
        if j != i:
            d[[i, j]] = d[[j, i]]
        if d[i, i] != 1:
            f = gf256.inv(int(d[i, i]))
            d[:, i] = [gf256.mul(f, int(x)) for x in d[:, i]]
        for c in range(cols):
            e = int(d[i, c])
            if c != i and e:
                d[:, c] = [int(x) ^ gf256.mul(e, int(y))
                           for x, y in zip(d[:, c], d[:, i])]
    # row `cols` (the first coding row) becomes all ones
    for c in range(cols):
        e = int(d[cols, c])
        if e != 1:
            f = gf256.inv(e)
            d[cols:, c] = [gf256.mul(f, int(x)) for x in d[cols:, c]]
    # and every later coding row starts with a one
    for r in range(cols + 1, rows):
        e = int(d[r, 0])
        if e != 1:
            f = gf256.inv(e)
            d[r] = [gf256.mul(f, int(x)) for x in d[r]]
    return d


def coding_matrix(k: int, m: int) -> np.ndarray:
    """The m x k coding rows of reed_sol_van."""
    return distribution_matrix(k + m, k)[k:]


def encode(data: np.ndarray, k: int, m: int) -> np.ndarray:
    """(stripes, k, C) data chunks -> (stripes, m, C) parity."""
    return gf256.matmul_regions(coding_matrix(k, m), data, axis=1)


def parity(data: np.ndarray, profile: dict) -> np.ndarray:
    """The parity of a ``technique=reed_sol_van`` profile."""
    if profile.get("technique", "reed_sol_van") != "reed_sol_van":
        raise NotImplementedError(profile["technique"])
    return encode(data, int(profile["k"]), int(profile["m"]))
