"""Plain GF(2^8) arithmetic for the references: polynomial 0x11D, the
field jerasure and ISA-L use at w=8 (the inverse of 2 is 142).

Everything is numpy over uint8 regions; a 256 x 256 product table
makes a region times a constant one gather.
"""

from __future__ import annotations

import numpy as np

POLY = 0x11D


def _tables():
    exp = np.zeros(512, np.int64)
    log = np.zeros(256, np.int64)
    v = 1
    for i in range(255):
        exp[i] = v
        log[v] = i
        v <<= 1
        if v & 0x100:
            v ^= POLY
    exp[255:510] = exp[:255]
    mul = np.zeros((256, 256), np.uint8)
    nz = np.arange(1, 256)
    for a in range(1, 256):
        mul[a, 1:] = exp[log[a] + log[nz]]
    return exp, log, mul


EXP, LOG, MUL = _tables()


def mul(a: int, b: int) -> int:
    return int(MUL[a, b])


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def div(a: int, b: int) -> int:
    return mul(a, inv(b))


def scale(c: int, region: np.ndarray) -> np.ndarray:
    """c * region, elementwise."""
    return MUL[int(c)][region]


def matmul_regions(matrix, regions: np.ndarray, axis: int) -> np.ndarray:
    """out[j] = XOR_i matrix[j][i] * regions[i] along ``axis``."""
    matrix = np.asarray(matrix)
    x = np.moveaxis(regions, axis, 0)
    out = np.zeros((matrix.shape[0],) + x.shape[1:], np.uint8)
    for j in range(matrix.shape[0]):
        for i in range(matrix.shape[1]):
            c = int(matrix[j, i])
            if c:
                out[j] ^= MUL[c][x[i]]
    return np.moveaxis(out, 0, axis)


def invert(matrix) -> np.ndarray:
    """Gauss-Jordan inverse of a square GF(2^8) matrix."""
    a = [list(map(int, row)) for row in np.asarray(matrix)]
    n = len(a)
    b = [[int(i == j) for j in range(n)] for i in range(n)]
    for col in range(n):
        piv = next((r for r in range(col, n) if a[r][col]), None)
        if piv is None:
            raise ValueError("singular matrix")
        a[col], a[piv] = a[piv], a[col]
        b[col], b[piv] = b[piv], b[col]
        f = inv(a[col][col])
        a[col] = [mul(f, v) for v in a[col]]
        b[col] = [mul(f, v) for v in b[col]]
        for r in range(n):
            if r != col and a[r][col]:
                g = a[r][col]
                a[r] = [x ^ mul(g, y) for x, y in zip(a[r], a[col])]
                b[r] = [x ^ mul(g, y) for x, y in zip(b[r], b[col])]
    return np.asarray(b, np.int64)
