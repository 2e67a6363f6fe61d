"""Plain reference of the ISA-L ``cauchy`` code (``plugin=isa
technique=cauchy``), w=8.

The coding matrix follows ISA-L's ``gf_gen_cauchy1_matrix``
(erasure_code/ec_base.c): the (k+m) x k matrix has the identity on top,
and coding row i (i = k .. k+m-1) holds ``a[i][j] = inv(i ^ j)`` over
GF(2^8) with polynomial 0x11D.  The Ceph isa plugin hands rows k.. to
the encoder, so parity is those m rows times the data chunks, byte by
byte.
"""

from __future__ import annotations

import numpy as np

from . import gf256


def coding_matrix(k: int, m: int) -> np.ndarray:
    """The m x k coding rows of gf_gen_cauchy1_matrix."""
    return np.array([[gf256.inv(i ^ j) for j in range(k)]
                     for i in range(k, k + m)], np.int64)


def encode(data: np.ndarray, k: int, m: int) -> np.ndarray:
    """(stripes, k, C) data chunks -> (stripes, m, C) parity."""
    return gf256.matmul_regions(coding_matrix(k, m), data, axis=1)


def parity(data: np.ndarray, profile: dict) -> np.ndarray:
    """The parity of a ``technique=cauchy`` profile."""
    if profile.get("technique") != "cauchy":
        raise NotImplementedError(profile.get("technique"))
    return encode(data, int(profile["k"]), int(profile["m"]))
