"""Plain reference of the clay (coupled-layer MSR) code at w=8.

Geometry as in Ceph's ErasureCodeClay: q = d-k+1, nu virtual zero
data nodes pad k+m to a multiple of q, t = (k+m+nu)/q, and a chunk is
q^t sub-chunks (planes).  Node n sits at (x, y) = (n % q, n // q).  In
plane z, a vertex whose y-th base-q digit of z equals x is a "dot" and
stores its uncoupled value; any other vertex pairs with node (z_y, y)
in plane z' (z with digit y set to x), and the pair stores
[C_a; C_b] = PFT [U_a; U_b], slot a being the smaller x and PFT the
2x2 reed_sol_van coding matrix.  In every plane the uncoupled values
of all nodes form one codeword of the scalar (k+nu, m) reed_sol_van
code.

The reference covers layouts whose data nodes (with the virtual ones)
and parity nodes each fill whole y-columns, as k=8 m=4 d=11 does:
then the data vertices pair only with data vertices, and encoding is
three plain passes -- uncouple the data, apply the scalar code plane
by plane, couple the parity.
"""

from __future__ import annotations

import numpy as np

from . import gf256, jerasure


class Geometry:
    def __init__(self, k: int, m: int, d: int) -> None:
        self.k, self.m, self.d = k, m, d
        self.q = d - k + 1
        self.nu = (self.q - (k + m) % self.q) % self.q
        self.t = (k + m + self.nu) // self.q
        self.sub = self.q ** self.t
        self.kk = k + self.nu
        if self.kk % self.q:
            raise NotImplementedError(
                "reference covers data and parity filling whole columns")
        self.pft = jerasure.coding_matrix(2, 2)
        self.pft_inv = gf256.invert(self.pft)
        self.mds = jerasure.coding_matrix(self.kk, m)

    def partner(self, node: int, z: int):
        """(partner node, partner plane, own slot) or None for a dot."""
        x, y = node % self.q, node // self.q
        xp = (z // self.q ** y) % self.q
        if xp == x:
            return None
        return (y * self.q + xp, z + (x - xp) * self.q ** y,
                0 if x < xp else 1)


def encode(data: np.ndarray, k: int, m: int, d: int) -> np.ndarray:
    """(stripes, k, C) data chunks -> (stripes, m, C) clay parity."""
    g = Geometry(k, m, d)
    s, _, chunk = data.shape
    sc = chunk // g.sub
    c = np.zeros((g.kk, g.sub, s, sc), np.uint8)
    c[:k] = data.reshape(s, k, g.sub, sc).transpose(1, 2, 0, 3)
    u = np.empty_like(c)
    for n in range(g.kk):
        for z in range(g.sub):
            p = g.partner(n, z)
            if p is None:
                u[n, z] = c[n, z]
                continue
            pn, pz, slot = p
            pair = (c[n, z], c[pn, pz]) if slot == 0 else (c[pn, pz], c[n, z])
            u[n, z] = (gf256.scale(g.pft_inv[slot, 0], pair[0])
                       ^ gf256.scale(g.pft_inv[slot, 1], pair[1]))
    up = gf256.matmul_regions(g.mds, u, axis=0)
    out = np.empty((m, g.sub, s, sc), np.uint8)
    for j in range(m):
        n = g.kk + j
        for z in range(g.sub):
            p = g.partner(n, z)
            if p is None:
                out[j, z] = up[j, z]
                continue
            pn, pz, slot = p
            pu = up[pn - g.kk, pz]
            pair = (up[j, z], pu) if slot == 0 else (pu, up[j, z])
            out[j, z] = (gf256.scale(g.pft[slot, 0], pair[0])
                         ^ gf256.scale(g.pft[slot, 1], pair[1]))
    return out.transpose(2, 0, 1, 3).reshape(s, m, chunk)


def parity(data: np.ndarray, profile: dict) -> np.ndarray:
    """The parity of a clay profile (jerasure scalar code)."""
    if profile.get("scalar_mds", "jerasure") != "jerasure":
        raise NotImplementedError(profile["scalar_mds"])
    k, m = int(profile["k"]), int(profile["m"])
    return encode(data, k, m, int(profile.get("d", k + m - 1)))
