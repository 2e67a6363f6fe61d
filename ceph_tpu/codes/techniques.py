"""Shared compute machinery for matrix / bitmatrix erasure codes.

Factored out of the jerasure and isa plugins (both reference plugins use
the same underlying jerasure/gf-complete region ops; here both use the
same numpy/XLA paths):

- MatrixCodeMixin    — GF(2^w)-element matrix codes (reed_sol_van,
  reed_sol_r6_op, isa reed_sol_van/cauchy). Encode/decode = word-wise
  GF(2^w) matrix application (jerasure_matrix_encode/decode semantics).
- BitmatrixCodeMixin — GF(2) bitmatrix codes in jerasure packet layout
  (cauchy_*, liberation, blaum_roth, liber8tion, shec).

Path selection: below ``min_xla_bytes`` the numpy reference region ops run
(no trace/compile cost); above it, the jit XLA path. Both are byte-
identical and cross-pinned in tests.

Decode-matrix caches are two-level: a per-instance dict (reset by
prepare(), mirroring ErasureCodeIsaTableCache) in front of the
process-wide engine.PatternCache, so a FRESH plugin instance with the
same profile reuses both the composed matrix and the already-traced
jit program for every erasure pattern seen before (the unified decode
engine's warm path; docs/PERF.md).
"""

from __future__ import annotations

import numpy as np

from ..ops import regionops
from ..ops.pallas_gf import apply_bitmatrix_best, apply_matrix_best
from ..utils.debug import DeviceVerificationError, verification_enabled
from ..utils.perf import global_perf
from ..ops.xla_ops import (
    apply_bitmatrix_xla,
    apply_matrix_xla,
    bitmatrix_to_static,
    jax_bytes_view,
    jax_words_view,
    matrix_to_static,
)


def _numpy_tier() -> bool:
    """True when the fallback policy has dropped to the numpy ground
    truth (no XLA backend initializes, or CEPH_TPU_ENGINE=numpy) — the
    batched paths must then never dispatch through jax at any size."""
    from ..ops.fallback import global_policy
    return global_policy().engine() == "numpy"


def apply_matrix_batch(chunks: np.ndarray, matrix: np.ndarray,
                       matrix_static, w: int,
                       min_xla_bytes: int = 1 << 20) -> np.ndarray:
    """The host-array matrix apply every GF(2^w) matrix code's
    ``*_chunks_batch`` surface shares (MatrixCodeMixin, clay's
    composites): below ``min_xla_bytes`` (or on the numpy tier) the
    host schedule runs, above it one device dispatch through
    :func:`apply_matrix_best`."""
    from ..telemetry.metrics import record_dispatch
    perf = global_perf()
    words = regionops.words_view(np.ascontiguousarray(chunks), w)
    if chunks.nbytes < min_xla_bytes or _numpy_tier():
        perf.inc("ec_host_calls")
        perf.inc("ec_host_bytes", chunks.nbytes)
        with record_dispatch("ec_apply", path="host"):
            # the numpy tier executes the IDENTICAL XOR schedule
            # the device kernels run when the probe prefers one
            # (ops/xor_schedule.py); regionops stays the ground
            # truth for everything else — byte-identical either
            # way (corpus + fuzz pinned)
            from ..ops.xor_schedule import host_matrix_apply
            return host_matrix_apply(
                np.ascontiguousarray(chunks), matrix, matrix_static, w)
    perf.inc("ec_device_calls")
    perf.inc("ec_device_bytes", chunks.nbytes)
    with perf.timed("ec_device_time"), \
            record_dispatch("ec_apply", path="device"):
        out = np.asarray(
            apply_matrix_best(words, matrix_static, w)).view(np.uint8)
    if verification_enabled():
        ref = regionops.matrix_encode(words, matrix, w).view(np.uint8)
        if not np.array_equal(out, ref):
            raise DeviceVerificationError(
                "device matrix path diverged from host ground truth "
                f"(w={w}, shape={chunks.shape})")
    return out


class MatrixCodeMixin:
    """Compute paths for GF(2^w)-element matrix codes.

    Requires: self.k, self.m, self.w, and build_matrix() -> (m, k) matrix.
    """

    min_xla_bytes = 1 << 20

    def build_matrix(self) -> np.ndarray:
        raise NotImplementedError

    def prepare(self) -> None:
        self.matrix = self.build_matrix()
        self._matrix_static = matrix_to_static(self.matrix)
        self._decode_cache: dict = {}

    def _apply(self, chunks: np.ndarray, matrix: np.ndarray,
               matrix_static) -> np.ndarray:
        return apply_matrix_batch(chunks, matrix, matrix_static, self.w,
                                  self.min_xla_bytes)

    def encode_chunks_batch(self, data: np.ndarray) -> np.ndarray:
        return self._apply(data, self.matrix, self._matrix_static)

    def _decode_matrix(self, available: tuple, erased: tuple):
        key = (available, erased)
        hit = self._decode_cache.get(key)
        if hit is None:
            from .engine import global_pattern_cache, pattern_key

            def build():
                survivors = list(available[:self.k])
                dm = regionops.matrix_decode_matrix(
                    self.matrix, self.k, survivors, list(erased), self.w)
                return (dm, matrix_to_static(dm), len(survivors))

            hit = global_pattern_cache().get_or_build(
                pattern_key(self, "matrix-decode", available, erased),
                build)
            self._decode_cache[key] = hit
        return hit

    def decode_chunks_batch(self, chunks: np.ndarray, available: tuple,
                            erased: tuple) -> np.ndarray:
        if len(available) < self.k:
            raise IOError(f"need {self.k} chunks, have {len(available)}")
        dm, dm_static, ns = self._decode_matrix(tuple(available), tuple(erased))
        return self._apply(np.ascontiguousarray(chunks[..., :ns, :]), dm,
                           dm_static)

    # -- device-resident paths (jax array in, jax array out; no host copy) --

    def encode_chunks_jax(self, data):
        """(batch, k, C) uint8 device array -> (batch, m, C) parity on device."""
        words = jax_words_view(data, self.w)
        return jax_bytes_view(
            apply_matrix_best(words, self._matrix_static, self.w))

    def decode_chunks_jax(self, chunks, available: tuple, erased: tuple):
        """(batch, len(available), C) device array -> (batch, len(erased), C)."""
        if len(available) < self.k:
            raise IOError(f"need {self.k} chunks, have {len(available)}")
        _, dm_static, ns = self._decode_matrix(tuple(available), tuple(erased))
        words = jax_words_view(chunks[..., :ns, :], self.w)
        return jax_bytes_view(apply_matrix_best(words, dm_static, self.w))

    # -- operand-coefficient decode (the pattern-generic serve path) -----

    def decode_rows(self, available: tuple, erased: tuple) -> np.ndarray:
        """The (len(erased), k) uint8 decode rows of one pattern: the
        GF(2^8) matrix taking the survivors ``available[:k]``, stacked
        in that order, to the erased chunks (w=8)."""
        if len(available) < self.k:
            raise IOError(f"need {self.k} chunks, have {len(available)}")
        return regionops.matrix_decode_matrix(
            self.matrix, self.k, list(available[:self.k]), list(erased),
            self.w).astype(np.uint8)

    def decode_chunks_operand_jax(self, chunks, coef):
        """(B, >= k, C) uint8 survivors + (B, e, k) uint8 per-stripe
        decode rows (:meth:`decode_rows`) -> (B, e, C): stripes of
        different erasure patterns in one device program (w=8)."""
        from ..ops.pallas_gf import apply_matrix_operand_best
        return apply_matrix_operand_best(chunks[:, :self.k, :], coef)

    def decode_chunks_operand_batch(self, chunks: np.ndarray,
                                    coef: np.ndarray) -> np.ndarray:
        """Numpy twin of :meth:`decode_chunks_operand_jax`."""
        from ..ops.pallas_gf import host_apply_operand
        return host_apply_operand(np.asarray(chunks)[:, :self.k], coef)

    # -- ragged paged surfaces (ISSUE 18: serve/pool.py page pools) ------

    def page_unit(self) -> int:
        """Page-size quantum for the paged serving pool: pages must
        hold whole GF(2^w) field elements so the word views stay free
        (matrix-code column locality is element-granular)."""
        return max(1, self.w // 8)

    def encode_chunks_ragged_jax(self, pool, mask):
        """Page-pool encode: (P, k, page_size) uint8 pool + (P,) {0,1}
        activity mask -> (P, m, page_size) parity, dead pages zero.
        The TRUE ragged kernel family (ops/pallas_gf.py) — the mask is
        a traced operand, so one program serves every occupancy."""
        from ..ops.pallas_gf import apply_matrix_best_ragged
        words = jax_words_view(pool, self.w)
        return jax_bytes_view(apply_matrix_best_ragged(
            words, self._matrix_static, mask, self.w))

    def decode_chunks_ragged_jax(self, pool, mask, available: tuple,
                                 erased: tuple):
        """Page-pool decode: (P, n_avail, page_size) survivors + mask
        -> (P, n_erased, page_size), dead pages zero."""
        if len(available) < self.k:
            raise IOError(f"need {self.k} chunks, have {len(available)}")
        from ..ops.pallas_gf import apply_matrix_best_ragged
        _, dm_static, ns = self._decode_matrix(tuple(available), tuple(erased))
        words = jax_words_view(pool[..., :ns, :], self.w)
        return jax_bytes_view(apply_matrix_best_ragged(
            words, dm_static, mask, self.w))

    # -- packed resident layout (ops/pallas_gf.py pack_chunks form) ------

    def encode_chunks_packed_jax(self, words):
        """(batch, k, R, 128) uint32 packed device array -> packed
        parity (batch, m, R, 128).  w=8 only; the fastest layout for
        device-resident chains (no pack/unpack anywhere)."""
        if self.w != 8:
            raise ValueError("packed layout is w=8 only")
        from ..ops.pallas_gf import apply_matrix_packed_best
        return apply_matrix_packed_best(words, self._matrix_static)

    def decode_chunks_packed_jax(self, words, available: tuple,
                                 erased: tuple):
        """Packed-layout decode: (batch, n_avail, R, 128) uint32 ->
        (batch, len(erased), R, 128)."""
        if self.w != 8:
            raise ValueError("packed layout is w=8 only")
        if len(available) < self.k:
            raise IOError(f"need {self.k} chunks, have {len(available)}")
        from ..ops.pallas_gf import apply_matrix_packed_best
        _, dm_static, ns = self._decode_matrix(tuple(available), tuple(erased))
        return apply_matrix_packed_best(words[..., :ns, :, :], dm_static)


class BitmatrixCodeMixin:
    """Compute paths for GF(2) bitmatrix codes in jerasure packet layout.

    Requires: self.k, self.m, self.w, self.packetsize, and
    build_bitmatrix() -> (m*w, k*w) 0/1 matrix.
    """

    min_xla_bytes = 1 << 20

    def build_bitmatrix(self) -> np.ndarray:
        raise NotImplementedError

    def prepare(self) -> None:
        self.bitmatrix = self.build_bitmatrix()
        self._bitmatrix_static = bitmatrix_to_static(self.bitmatrix)
        self._decode_cache: dict = {}

    def _apply(self, chunks: np.ndarray, bitmatrix: np.ndarray,
               bitmatrix_static) -> np.ndarray:
        from ..telemetry.metrics import record_dispatch
        perf = global_perf()
        if chunks.nbytes < self.min_xla_bytes or _numpy_tier():
            perf.inc("ec_host_calls")
            perf.inc("ec_host_bytes", chunks.nbytes)
            with record_dispatch("ec_apply", path="host"):
                return regionops.bitmatrix_encode(
                    chunks, bitmatrix, self.w, self.packetsize)
        perf.inc("ec_device_calls")
        perf.inc("ec_device_bytes", chunks.nbytes)
        with perf.timed("ec_device_time"), \
                record_dispatch("ec_apply", path="device"):
            out = np.asarray(apply_bitmatrix_best(
                chunks, bitmatrix_static, self.w, self.packetsize))
        if verification_enabled():
            ref = regionops.bitmatrix_encode(chunks, bitmatrix, self.w,
                                             self.packetsize)
            if not np.array_equal(out, ref):
                raise DeviceVerificationError(
                    "device bitmatrix path diverged from host ground "
                    f"truth (w={self.w}, shape={chunks.shape})")
        return out

    def encode_chunks_batch(self, data: np.ndarray) -> np.ndarray:
        return self._apply(np.ascontiguousarray(data), self.bitmatrix,
                           self._bitmatrix_static)

    def _decode_bitmatrix(self, available: tuple, erased: tuple):
        key = (available, erased)
        hit = self._decode_cache.get(key)
        if hit is None:
            from .engine import global_pattern_cache, pattern_key

            def build():
                survivors = list(available[:self.k])
                dm = regionops.bitmatrix_decode_matrix(
                    self.bitmatrix, self.k, self.w, survivors,
                    list(erased))
                return (dm, bitmatrix_to_static(dm), len(survivors))

            hit = global_pattern_cache().get_or_build(
                pattern_key(self, "bitmatrix-decode", available, erased),
                build)
            self._decode_cache[key] = hit
        return hit

    def decode_chunks_batch(self, chunks: np.ndarray, available: tuple,
                            erased: tuple) -> np.ndarray:
        if len(available) < self.k:
            raise IOError(f"need {self.k} chunks, have {len(available)}")
        dm, dm_static, ns = self._decode_bitmatrix(tuple(available),
                                                   tuple(erased))
        return self._apply(np.ascontiguousarray(chunks[..., :ns, :]), dm,
                           dm_static)

    # -- device-resident paths (jax array in, jax array out; no host copy) --

    def encode_chunks_jax(self, data):
        """(batch, k, C) uint8 device array -> (batch, m, C) parity on device."""
        return apply_bitmatrix_best(data, self._bitmatrix_static, self.w,
                                    self.packetsize)

    def decode_chunks_jax(self, chunks, available: tuple, erased: tuple):
        """(batch, len(available), C) device array -> (batch, len(erased), C)."""
        if len(available) < self.k:
            raise IOError(f"need {self.k} chunks, have {len(available)}")
        _, dm_static, ns = self._decode_bitmatrix(tuple(available),
                                                  tuple(erased))
        return apply_bitmatrix_best(chunks[..., :ns, :], dm_static, self.w,
                                    self.packetsize)

    # -- ragged paged surfaces (ISSUE 18) --------------------------------

    def page_unit(self) -> int:
        """Bitmatrix codes mix across the w packets of one
        w*packetsize block but never across blocks — the block is the
        column-locality quantum, so every pool page must hold whole
        blocks."""
        return self.w * self.packetsize

    def encode_chunks_ragged_jax(self, pool, mask):
        """Page-pool bitmatrix encode: mask-gate the pool (pure GF
        scaling, see ops/pallas_gf.py::mask_pages) and run the packet
        kernel family on the page batch — dead pages zero by XOR
        linearity."""
        from ..ops.pallas_gf import mask_pages
        return apply_bitmatrix_best(mask_pages(pool, mask),
                                    self._bitmatrix_static, self.w,
                                    self.packetsize)

    def decode_chunks_ragged_jax(self, pool, mask, available: tuple,
                                 erased: tuple):
        """Page-pool bitmatrix decode, dead pages zero."""
        if len(available) < self.k:
            raise IOError(f"need {self.k} chunks, have {len(available)}")
        from ..ops.pallas_gf import mask_pages
        _, dm_static, ns = self._decode_bitmatrix(tuple(available),
                                                  tuple(erased))
        return apply_bitmatrix_best(
            mask_pages(pool[..., :ns, :], mask), dm_static, self.w,
            self.packetsize)
