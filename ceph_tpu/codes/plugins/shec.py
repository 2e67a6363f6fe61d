"""shec-equivalent plugin — Shingled Erasure Code (locally repairable).

Mirrors src/erasure-code/shec/ErasureCodeShec.{h,cc} +
ErasureCodeShecTableCache.{h,cc} + ErasureCodePluginShec.cc:
- class ErasureCodeShec / ErasureCodeShecReedSolomonVandermonde
  (technique=single|multiple), profile k, m, c, w in {8, 16, 32}.
- shec_reedsolomon_coding_matrix -> _shec_coding_matrix: an (m, k)
  GF(2^w) matrix where parity i covers only a shingled window of
  l = ceil(k*c/m) data chunks (stride floor(i*k/m), wrapping mod k), so
  every data chunk is covered by >= c parities; coefficients come from
  the Vandermonde RS matrix restricted to the window.
- shec_minimum_to_decode / shec_make_decoding_matrix -> the generic
  minimum-read search over parity subsets in ceph_tpu.codes.linear
  (the cover-problem search SURVEY.md §2.1 describes), composed into ONE
  batched GF matrix application for the TPU hot path.

Provenance caveat (SURVEY.md §0: reference mount unreadable): the window
layout and coefficient choice follow the SHEC paper + upstream structure;
the cross-implementation byte-identity of parity cannot be verified until
the reference is readable. Round-trip correctness, the c-coverage
property, and single-failure read locality are pinned by tests.
"""

from __future__ import annotations

import functools

import numpy as np

from ...matrices.jerasure import reed_sol_vandermonde_coding_matrix
from ..base import ErasureCode
from ..linear import DecodePlan, decode_plan
from ..registry import ERASURE_CODE_VERSION, ErasureCodePlugin
from ..techniques import MatrixCodeMixin

__erasure_code_version__ = ERASURE_CODE_VERSION

LARGEST_VECTOR_WORDSIZE = 16
SIZEOF_INT = 4


@functools.lru_cache(maxsize=64)
def _shec_coding_matrix(k: int, m: int, c: int, w: int) -> np.ndarray:
    """(m, k) shingled coding matrix (shec_reedsolomon_coding_matrix).

    Parity row i keeps the Vandermonde coefficients on its shingle window
    {(floor(i*k/m) + t) mod k : t < ceil(k*c/m)} and is zero elsewhere.
    m == c degenerates to the dense MDS matrix (every window is all of
    [0, k), matching upstream's "replicated" corner).
    """
    base = reed_sol_vandermonde_coding_matrix(k, m, w)
    if m == 1 or c == m:
        return base
    l = -(-k * c // m)  # ceil(k*c/m): shingle width
    mat = np.zeros_like(base)
    for i in range(m):
        start = (i * k) // m
        for t in range(l):
            j = (start + t) % k
            mat[i, j] = base[i, j]
    return mat


class ErasureCodeShecTableCache:
    """ErasureCodeShecTableCache.{h,cc} — decode-plan cache per pattern.

    The reference caches jerasure decoding tables keyed by erasure
    pattern; here the expensive artifacts are the minimum-read plan
    search, the composed decode matrix (host) and its jit trace
    (device), keyed the same way.  Two-level like the mixin caches: a
    per-instance dict in front of the process-wide engine.PatternCache
    (``owner`` supplies the profile key), so fresh instances with the
    same profile skip the cover-problem search entirely.
    """

    def __init__(self, owner=None) -> None:
        self._plans: dict = {}
        self._owner = owner

    def get_plan(self, matrix: np.ndarray, k: int, w: int,
                 available: frozenset, want: frozenset) -> DecodePlan:
        key = (available, want)
        plan = self._plans.get(key)
        if plan is None:
            if self._owner is not None:
                from ..engine import global_pattern_cache, pattern_key
                plan = global_pattern_cache().get_or_build(
                    pattern_key(self._owner, "shec-plan",
                                tuple(sorted(available)),
                                tuple(sorted(want))),
                    lambda: decode_plan(matrix, k, w, available, want))
            else:
                plan = decode_plan(matrix, k, w, available, want)
            self._plans[key] = plan
        return plan


class ErasureCodeShec(MatrixCodeMixin, ErasureCode):
    """ErasureCodeShec.{h,cc} — base shec semantics."""

    DEFAULT_K = "4"
    DEFAULT_M = "3"
    DEFAULT_C = "2"
    DEFAULT_W = 8

    def __init__(self, technique: str = "multiple") -> None:
        super().__init__()
        self.technique = technique
        self.c = 0
        self.w = self.DEFAULT_W

    def parse(self, profile) -> None:
        """ErasureCodeShec::parse: k/m/c required relations, w gate."""
        self.k = self.to_int("k", profile, self.DEFAULT_K)
        self.m = self.to_int("m", profile, self.DEFAULT_M)
        self.c = self.to_int("c", profile, self.DEFAULT_C)
        self.w = self.to_int("w", profile, str(self.DEFAULT_W))
        self.sanity_check_k_m(self.k, self.m)
        if self.c < 1:
            raise ValueError(f"c={self.c} must be >= 1")
        if self.c > self.m:
            raise ValueError(f"c={self.c} must be <= m={self.m}")
        if self.m > self.k:
            raise ValueError(f"m={self.m} must be <= k={self.k}")
        if self.w not in (8, 16, 32):
            raise ValueError(f"w={self.w} must be one of 8, 16, 32")
        if self.k + self.m > (1 << self.w):
            raise ValueError(
                f"k+m={self.k + self.m} must be <= 2^w={1 << self.w}")

    def prepare(self) -> None:
        super().prepare()  # MatrixCodeMixin: matrix + static + caches
        self.tcache = ErasureCodeShecTableCache(self)
        self._windows = [frozenset(int(j) for j in np.nonzero(self.matrix[i])[0])
                         for i in range(self.m)]

    def build_matrix(self) -> np.ndarray:
        return _shec_coding_matrix(self.k, self.m, self.c, self.w)

    def get_alignment(self) -> int:
        """ErasureCodeShec::get_alignment (vandermonde-style padding)."""
        alignment = self.k * self.w * SIZEOF_INT
        if (self.w * SIZEOF_INT) % LARGEST_VECTOR_WORDSIZE:
            alignment = self.k * self.w * LARGEST_VECTOR_WORDSIZE
        return alignment

    def get_chunk_size(self, stripe_width: int) -> int:
        alignment = self.get_alignment()
        tail = stripe_width % alignment
        padded = stripe_width + (alignment - tail if tail else 0)
        assert padded % self.k == 0
        return padded // self.k

    # -- recovery (ErasureCodeShec::shec_minimum_to_decode / decode) --------

    def minimum_to_decode(self, want_to_read: set, available: set):
        plan = self.tcache.get_plan(self.matrix, self.k, self.w,
                                    frozenset(available),
                                    frozenset(want_to_read))
        return {c: [(0, 1)] for c in plan.reads}

    def decode(self, want_to_read: set, chunks, chunk_size: int):
        """Plan-driven decode: one batched matrix application over the
        minimum read set (upstream zero-fills and runs the jerasure
        decode; the bytes produced are the same solved linear system)."""
        available = frozenset(chunks)
        want = frozenset(want_to_read)
        if want <= available:
            return {i: chunks[i] for i in sorted(want)}
        plan = self.tcache.get_plan(self.matrix, self.k, self.w,
                                    available, want)
        stack = np.stack([np.frombuffer(chunks[c], dtype=np.uint8)
                          for c in plan.reads])
        out = self._apply_plan(plan, stack[None])[0]
        return {c: out[t].tobytes() for t, c in enumerate(plan.want_order)}

    def decode_chunks(self, want_to_read: set, chunks, decoded):
        out = self.decode(set(want_to_read), dict(chunks),
                          len(next(iter(chunks.values()))))
        decoded.update(out)
        return decoded

    def decode_chunks_batch(self, chunks: np.ndarray, available: tuple,
                            erased: tuple) -> np.ndarray:
        """(batch, len(available), C) -> (batch, len(erased), C)."""
        plan = self.tcache.get_plan(self.matrix, self.k, self.w,
                                    frozenset(available), frozenset(erased))
        aidx = {c: t for t, c in enumerate(available)}
        sel = np.array([aidx[c] for c in plan.reads])
        out = self._apply_plan(plan, np.ascontiguousarray(chunks[:, sel, :]))
        worder = {c: t for t, c in enumerate(plan.want_order)}
        keep = np.array([worder[c] for c in erased])
        return np.ascontiguousarray(out[:, keep, :])

    def _plan_static(self, plan: DecodePlan):
        """(matrix, static, n_reads) for a plan — the per-pattern
        composite artifact, shared cross-instance through the engine
        pattern cache so repeat plans hit warm jit traces."""
        key = (plan.reads, plan.want_order)
        cache = self._decode_cache
        hit = cache.get(key)
        if hit is None:
            from ...ops.xla_ops import matrix_to_static
            from ..engine import global_pattern_cache, pattern_key
            hit = global_pattern_cache().get_or_build(
                pattern_key(self, "shec-plan-static", plan.reads,
                            plan.want_order),
                lambda: (plan.matrix, matrix_to_static(plan.matrix),
                         len(plan.reads)))
            cache[key] = hit
        return hit

    def _apply_plan(self, plan: DecodePlan, stack: np.ndarray) -> np.ndarray:
        dm, dm_static, _ = self._plan_static(plan)
        return self._apply(stack, dm, dm_static)

    def decode_chunks_jax(self, chunks, available: tuple, erased: tuple):
        """Device-resident decode (bench path): plan once, one apply.

        apply_matrix_best, not the raw XLA path: the XLA w=8 SWAR
        branch bitcasts u8<->u32 in HBM, which is a full relayout on
        TPU (u8 tiles (32,128) vs u32 (8,128)) costing ~3x the math —
        the Pallas byte kernel packs in-registers instead (the same
        lesson the encode path learned in round 3; this was the shec
        decode row's 17 GB/s bottleneck)."""
        from ...ops.pallas_gf import apply_matrix_best
        from ...ops.xla_ops import (jax_bytes_view, jax_words_view,
                                    take_static)
        plan = self.tcache.get_plan(self.matrix, self.k, self.w,
                                    frozenset(available), frozenset(erased))
        aidx = {c: t for t, c in enumerate(available)}
        sel = [aidx[c] for c in plan.reads]
        worder = {c: t for t, c in enumerate(plan.want_order)}
        _, dm_static, _ = self._plan_static(plan)
        # static column selection, not np fancy indexing: the plan's
        # read/want orders are trace-time constants, and a gather here
        # bakes a device_put + dynamic indirection into the program
        # (tpu-audit: audit-transfer)
        sub = take_static(chunks, sel, axis=1)
        words = jax_words_view(sub, self.w)
        out = apply_matrix_best(words, dm_static, self.w)
        out = jax_bytes_view(out)
        return take_static(out, [worder[c] for c in erased], axis=1)

    def decode_chunks_packed_jax(self, words, available: tuple,
                                 erased: tuple):
        """Packed-layout minimum-read decode: (batch, n_avail, R, 128)
        uint32 -> (batch, len(erased), R, 128) — the plan's composite
        matrix through the packed dispatch (the generalized Pallas
        kernel on TPU; plan shapes like (1, 7) ride the padded row
        tiles).  w=8 profiles only, like every packed path."""
        if self.w != 8:
            raise ValueError("packed layout is w=8 only")
        from ...ops.pallas_gf import apply_matrix_packed_best
        from ...ops.xla_ops import take_static
        plan = self.tcache.get_plan(self.matrix, self.k, self.w,
                                    frozenset(available), frozenset(erased))
        aidx = {c: t for t, c in enumerate(available)}
        sel = [aidx[c] for c in plan.reads]
        worder = {c: t for t, c in enumerate(plan.want_order)}
        _, dm_static, _ = self._plan_static(plan)
        out = apply_matrix_packed_best(take_static(words, sel, axis=1),
                                       dm_static)
        return take_static(out, [worder[c] for c in erased], axis=1)


class ErasureCodeShecReedSolomonVandermonde(ErasureCodeShec):
    """Named to mirror the reference's single concrete technique class."""


class ErasureCodePluginShec(ErasureCodePlugin):
    """ErasureCodePluginShec.cc -> factory (technique single|multiple)."""

    def factory(self, profile, directory=None):
        technique = profile.get("technique", "multiple")
        if technique not in ("single", "multiple"):
            raise ValueError(
                f"technique={technique} must be single or multiple")
        interface = ErasureCodeShecReedSolomonVandermonde(technique)
        interface.init(profile)
        return interface


def __erasure_code_init__(plugin_name: str, registry) -> None:
    registry.add(plugin_name, ErasureCodePluginShec())
