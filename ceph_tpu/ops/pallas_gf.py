"""Pallas GF(2^8) region kernels — the device performance path.

SURVEY.md §7 step 3 (north star: "GF(2^8) Reed-Solomon / Cauchy matrix
multiplies as Pallas bit-sliced kernels").  Replaces, at the math level,
gf-complete's SIMD region ops (src/erasure-code/jerasure/gf-complete ->
gf_w8_split_multiply_region_sse family) with a VMEM-resident SWAR
kernel.

Layout (measured on a v5e through profile_encode3.py): kernel I/O is
uint8 END TO END.  An HBM-side uint8<->uint32 bitcast around the kernel
is a full relayout (u8 tiles are (32,128), u32 tiles (8,128)) costing
~3x the kernel itself; instead each block loads u8 tiles and packs four
sublanes into one u32 SWAR word IN REGISTERS (pltpu.bitcast), runs the
xtime/XOR schedule, and unpacks on store.  The byte->word mapping is
private to the kernel and symmetric on input and output, and GF(2^8)
region math is byte-local, so any fixed bijection is exact.

- 4 independent GF(2^8) field bytes per uint32 VPU lane (TPUs have no
  byte gather; 32-bit lanes are native).
- Each grid step holds one (k, TILE) tile of the stripe batch in VMEM
  and XOR-folds xtime doubling planes straight into the m parity
  accumulators — data is read from HBM once and parity written once.
  (The XLA fallback in xla_ops.py expresses the same math, but
  materializes doubling planes between fusions at multi-MiB sizes.)
- The coding matrix is STATIC: the kernel is specialized (fully
  unrolled xtime/XOR schedule) per matrix, like jerasure's
  smart-schedule specialization per bitmatrix.
- Bitmatrix codes (cauchy_*, liberation, blaum_roth, liber8tion, shec)
  are pure packet XOR — no word packing at all; their kernel stays in
  uint8 throughout.
- w=16/32 matrix codes run through a separate word kernel on the
  uint16/uint32 word views (elements must stay whole inside SWAR
  registers; the byte kernel's strided packing is w=8-only).

Byte-identity: pinned against ops/regionops.py (the host ground truth)
in tests/test_pallas.py, in interpreter mode on CPU and compiled on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the SWAR doubling primitives, shared with the XLA path so the two
# engines can never diverge; the kernel's little-endian sublane packing
# keeps multi-byte field elements (w=16/32) contiguous per word
from .xla_ops import xtime_swar as _xtime_swar

LANE = 128            # TPU lane width
SUBLANE_U8 = 32       # uint8 VMEM tile is (32, 128)
SUBLANE_U32 = 8       # uint32 VMEM tile is (8, 128)
MAX_ROW_TILE8 = 512   # u8 rows of 128 lanes per block: 64 KiB per chunk


def _pallas_call(*args, **kwargs):
    """``pl.pallas_call`` applied with 64-bit types off: Mosaic lowers
    32-bit index maps only ("failed to legalize func.return" for a
    kernel traced with ``jax_enable_x64`` on, as a caller inside
    crush/bulk.py's ``with_x64`` scope or one that sets it globally
    would)."""
    call = pl.pallas_call(*args, **kwargs)

    def run(*operands):
        with jax.enable_x64(False):
            return call(*operands)

    return run


def _pack_words(tile, interpret: bool):
    """(4r, 128) uint8 tile -> (r, 128) uint32 SWAR words, in registers.

    On TPU this is a vreg reinterpret (pltpu.bitcast packs 4 sublanes
    per 32-bit sublane); the interpreter path emulates one fixed
    mapping.  Only symmetry with _unpack_words matters (see module
    docstring)."""
    if not interpret:
        return pltpu.bitcast(tile, jnp.uint32)
    r = tile.shape[0] // 4
    b = tile.reshape(r, 4, LANE).astype(jnp.uint32)
    return b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16) | (b[:, 3] << 24)


def _unpack_words(words, interpret: bool):
    """Inverse of _pack_words: (r, 128) uint32 -> (4r, 128) uint8."""
    if not interpret:
        return pltpu.bitcast(words, jnp.uint8)
    parts = jnp.stack([(words >> s) & 0xFF for s in (0, 8, 16, 24)],
                      axis=1)
    return parts.astype(jnp.uint8).reshape(words.shape[0] * 4, LANE)


def _matrix_kernel(matrix_t, s: int, r: int, w: int, pack, unpack):
    """Build THE specialized kernel body shared by every matrix-code
    variant — byte w=8, packed resident, and w=16/32 word layouts pass
    their own register pack/unpack pair: per input chunk j, walk the
    xtime doubling chain once and XOR plane t into every accumulator i
    whose matrix[i][j] has bit t."""

    def kernel(in_ref, out_ref):
        accs = [None] * r
        for j in range(s):
            col = [matrix_t[i][j] for i in range(r)]
            top = max((c.bit_length() for c in col), default=0)
            if top == 0:
                continue
            plane = pack(in_ref[0, j])
            for t in range(top):
                if t > 0:
                    plane = _xtime_swar(plane, w)
                for i in range(r):
                    if (col[i] >> t) & 1:
                        accs[i] = plane if accs[i] is None else accs[i] ^ plane
        zero = None
        for i in range(r):
            if accs[i] is None:
                if zero is None:
                    zero = jnp.zeros_like(in_ref[0, 0])
                out_ref[0, i] = zero
            else:
                out_ref[0, i] = unpack(accs[i])

    return kernel


def _gf8_matrix_kernel(matrix_t, s: int, r: int, interpret: bool,
                       packed: bool = False):
    """w=8 kernel body.  The register pack groups bytes strided by 128
    lanes — exact for byte-local GF(2^8) math but unusable for w=16/32
    (their elements would split; those use _gfw_matrix_kernel, which
    receives whole elements per sublane).  packed=True: blocks are
    already uint32 SWAR words (the resident layout) — identity
    pack/unpack."""
    ident = lambda v: v  # noqa: E731
    if packed:
        return _matrix_kernel(matrix_t, s, r, 8, ident, ident)
    return _matrix_kernel(
        matrix_t, s, r, 8,
        lambda v: _pack_words(v, interpret),
        lambda v: _unpack_words(v, interpret))


def _row_tile(rows: int, sublane: int, cap: int) -> int:
    """Largest multiple of ``sublane`` (the dtype's native VMEM tile
    sublane count) that divides ``rows``, capped; 0 when none exists
    (caller falls back to XLA)."""
    for cand in range(cap, sublane - 1, -sublane):
        if cand <= rows and rows % cand == 0:
            return cand
    return 0


def _row_tile8(rows: int, cap: int | None = None) -> int:
    return _row_tile(rows, SUBLANE_U8, cap or MAX_ROW_TILE8)


def tuned_row_tile_cap(packed: bool) -> int | None:
    """The autotuner's row-tile consultation seam (ISSUE 14): the
    tuned u8 row-tile cap for this layout from the installed
    best-config table, or None (= MAX_ROW_TILE8 byte-identically).
    The cap is a STATIC argument of the kernel wrappers, so a tuned
    value is part of the jit cache key: installed before warmup it
    costs nothing warm; installed mid-process it rebuilds once (the
    table install clears the pattern cache for exactly this reason)."""
    from ..tune.table import consult
    cfg = consult("row-tile", engine="pallas",
                  layout="packed" if packed else "bytes")
    if cfg:
        v = cfg.get("max_row_tile8")
        if (isinstance(v, int) and not isinstance(v, bool)
                and v >= SUBLANE_U8 and v % SUBLANE_U8 == 0):
            return v
    return None


def pallas_matrix_supported(shape, w: int) -> bool:
    """True when (..., s, C) uint8 chunks fit the byte kernel's
    tiling WITHOUT padding: w=8 and C a multiple of 32*128 bytes
    (every SIMD-aligned chunk size >= 4 KiB qualifies; others pad
    through pallas_matrix_padded_supported or fall back to the XLA
    path / the word kernel)."""
    if w != 8 or len(shape) < 2:
        return False
    c = shape[-1]
    if c % LANE != 0:
        return False
    return _row_tile8(c // LANE) != 0


def pallas_matrix_padded_supported(shape, w: int) -> bool:
    """The composite-matrix generalization of pallas_matrix_supported:
    any lane-aligned chunk size qualifies — row counts off the native
    u8 sublane tile are zero-padded up to it inside the kernel wrapper
    and the pad rows are masked off on writeback.  GF(2^8) region math
    is byte-local, so pad bytes never mix into real rows.  Shapes like
    clay's (..., 704, 2048) single-erasure composite (16 u8 rows, not
    a 32-row tile) land here."""
    if w != 8 or len(shape) < 2:
        return False
    c = shape[-1]
    return c > 0 and c % LANE == 0


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def apply_matrix_pallas(chunks: jax.Array, matrix_t,
                        interpret: bool = False,
                        row_tile_cap: int | None = None) -> jax.Array:
    """Apply a static (r, s) GF(2^8) matrix to (..., s, C) uint8
    chunks -> (..., r, C) parity/decode output.  Same contract as
    xla_ops.apply_matrix_xla (w=8); caller gates on
    pallas_matrix_padded_supported (row counts off the native sublane
    tile are zero-padded and the pad rows masked off on writeback).
    ``row_tile_cap`` (static): the autotuned VMEM row-tile ceiling —
    partitioning only, byte-identical at any legal value."""
    r = len(matrix_t)
    s = len(matrix_t[0])
    assert chunks.shape[-2] == s and chunks.dtype == jnp.uint8
    lead = chunks.shape[:-2]
    c = chunks.shape[-1]
    rows = c // LANE
    b = int(np.prod(lead)) if lead else 1
    tiles = chunks.reshape(b, s, rows, LANE)
    pad = (-rows) % SUBLANE_U8
    if pad:
        tiles = jnp.pad(tiles, ((0, 0), (0, 0), (0, pad), (0, 0)))
    prows = rows + pad
    rt = _row_tile8(prows, row_tile_cap)
    out = _pallas_call(
        _gf8_matrix_kernel(matrix_t, s, r, interpret),
        grid=(b, prows // rt),
        in_specs=[pl.BlockSpec((1, s, rt, LANE),
                               lambda i, j: (i, 0, j, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, r, rt, LANE),
                               lambda i, j: (i, 0, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, r, prows, LANE), jnp.uint8),
        interpret=interpret,
    )(tiles)
    if pad:
        out = out[..., :rows, :]
    return out.reshape(lead + (r, c))


# -- w=16/32 word kernel -------------------------------------------------
#
# Multi-byte field elements must stay whole inside the SWAR registers,
# so this kernel takes the w-bit WORD view (uint16/uint32 — a free
# numpy view on the host; the plugin mixins already pass it).  u16
# tiles bitcast in-registers to u32 pairs of complete elements; u32
# elements are SWAR words as-is.

_WORD_DTYPE = {16: jnp.uint16, 32: jnp.uint32}
_WORD_SUBLANE = {16: 16, 32: 8}   # native VMEM tile sublane counts


def _gfw_matrix_kernel(matrix_t, s: int, r: int, w: int, interpret: bool):
    def pack(tile):
        if w == 32:
            return tile
        if not interpret:
            return pltpu.bitcast(tile, jnp.uint32)
        half = tile.reshape(tile.shape[0] // 2, 2, LANE).astype(jnp.uint32)
        return half[:, 0] | (half[:, 1] << 16)

    def unpack(words):
        if w == 32:
            return words
        if not interpret:
            return pltpu.bitcast(words, jnp.uint16)
        parts = jnp.stack([words & 0xFFFF, words >> 16], axis=1)
        return parts.astype(jnp.uint16).reshape(words.shape[0] * 2, LANE)

    return _matrix_kernel(matrix_t, s, r, w, pack, unpack)


def _row_tile_words(rows: int, w: int) -> int:
    return _row_tile(rows, _WORD_SUBLANE[w], MAX_ROW_TILE8 // (w // 8))


def pallas_matrix_words_supported(shape, w: int) -> bool:
    """(..., s, Ce) word arrays whose element rows tile the word
    dtype's native VMEM sublanes."""
    if w not in (16, 32) or len(shape) < 2:
        return False
    ce = shape[-1]
    if ce % LANE != 0:
        return False
    return _row_tile_words(ce // LANE, w) != 0


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def apply_matrix_pallas_words(words: jax.Array, matrix_t, w: int,
                              interpret: bool = False) -> jax.Array:
    """Apply a static (r, s) GF(2^w) matrix (w=16/32) to (..., s, Ce)
    w-bit word arrays -> (..., r, Ce).  Same contract as
    xla_ops.apply_matrix_xla on word views; caller gates on
    pallas_matrix_words_supported."""
    r = len(matrix_t)
    s = len(matrix_t[0])
    assert words.shape[-2] == s and words.dtype == _WORD_DTYPE[w]
    lead = words.shape[:-2]
    ce = words.shape[-1]
    rows = ce // LANE
    rt = _row_tile_words(rows, w)
    b = int(np.prod(lead)) if lead else 1
    tiles = words.reshape(b, s, rows, LANE)
    out = _pallas_call(
        _gfw_matrix_kernel(matrix_t, s, r, w, interpret),
        grid=(b, rows // rt),
        in_specs=[pl.BlockSpec((1, s, rt, LANE),
                               lambda i, j: (i, 0, j, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, r, rt, LANE),
                               lambda i, j: (i, 0, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, r, rows, LANE),
                                       _WORD_DTYPE[w]),
        interpret=interpret,
    )(tiles)
    return out.reshape(lead + (r, ce))


# -- packed (resident words) layout --------------------------------------
#
# SURVEY.md §7 hard-part 3: "keep data in bit-plane layout across
# encode+decode".  The packed layout is the byte stream viewed as
# little-endian uint32 words tiled (rows, 128): pack_chunks/unpack_chunks
# are FREE numpy views on the host, and device arrays staged packed skip
# the kernel's register pack/unpack entirely — the fastest path for
# device-resident pipelines (chained encode/decode, the bench --loop
# mode).  Byte payloads are identical; only the declared dtype/shape
# differ.

def pack_chunks(chunks: np.ndarray) -> np.ndarray:
    """(..., s, C) uint8 host array -> (..., s, C/512, 128) uint32 view
    (no copy; C must satisfy pallas_matrix_supported)."""
    c = chunks.shape[-1]
    return np.ascontiguousarray(chunks).view(np.uint32).reshape(
        chunks.shape[:-1] + (c // (4 * LANE), LANE))


def unpack_chunks(words: np.ndarray) -> np.ndarray:
    """Inverse of pack_chunks: (..., s, R, 128) uint32 -> (..., s, C)."""
    r = words.shape[-2]
    return np.ascontiguousarray(words).view(np.uint8).reshape(
        words.shape[:-2] + (r * 4 * LANE,))


def pallas_matrix_packed_supported(shape) -> bool:
    """Packed-layout gate, post-generalization: ANY (..., s, R, 128)
    uint32 array qualifies — row counts off the native u32 sublane
    tile are zero-padded inside apply_matrix_pallas_packed and the pad
    rows masked off on writeback (the composite-matrix shapes: clay's
    per-sub-chunk 4-row tiles, shec/lrc minimum-read stacks)."""
    return len(shape) >= 3 and shape[-1] == LANE and shape[-2] >= 1


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def apply_matrix_pallas_packed(words: jax.Array, matrix_t,
                               interpret: bool = False,
                               row_tile_cap: int | None = None
                               ) -> jax.Array:
    """Packed-layout apply: (..., s, R, 128) uint32 -> (..., r, R, 128).
    Same math as apply_matrix_pallas (w=8), zero layout work,
    same (static) autotuned ``row_tile_cap`` seam.

    Accepts ARBITRARY (r, s) composite matrices and row counts: a row
    count off the native u32 sublane tile is zero-padded up to it and
    the pad rows are masked off on writeback — GF(2^8) region math is
    byte-local, so pad words never mix into real output rows."""
    r = len(matrix_t)
    s = len(matrix_t[0])
    assert words.shape[-3] == s and words.dtype == jnp.uint32
    assert words.shape[-1] == LANE
    lead = words.shape[:-3]
    rows = words.shape[-2]
    b = int(np.prod(lead)) if lead else 1
    tiles = words.reshape(b, s, rows, LANE)
    pad = (-rows) % SUBLANE_U32
    if pad:
        tiles = jnp.pad(tiles, ((0, 0), (0, 0), (0, pad), (0, 0)))
    prows = rows + pad
    rt = _row_tile8(prows * 4, row_tile_cap) // 4
    if rt == 0 or prows % rt:
        rt = prows  # small shapes: one block per chunk
    out = _pallas_call(
        _gf8_matrix_kernel(matrix_t, s, r, interpret, packed=True),
        grid=(b, prows // rt),
        in_specs=[pl.BlockSpec((1, s, rt, LANE),
                               lambda i, j: (i, 0, j, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, r, rt, LANE),
                               lambda i, j: (i, 0, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, r, prows, LANE), jnp.uint32),
        interpret=interpret,
    )(tiles)
    if pad:
        out = out[..., :rows, :]
    return out.reshape(lead + (r, rows, LANE))


def _packed_to_bytes(words: jax.Array):
    """(..., s, R, 128) uint32 -> (..., s, R*512) uint8 device bitcast
    (the byte view the XLA/MXU paths consume; same idiom the packed
    XLA fallback has always used, pinned byte-identical in tests)."""
    lead = words.shape[:-3]
    s, rows = words.shape[-3], words.shape[-2]
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(
        lead + (s, rows * 4 * LANE))


def _bytes_to_packed(chunks: jax.Array):
    """Inverse of _packed_to_bytes."""
    lead = chunks.shape[:-2]
    r, c = chunks.shape[-2], chunks.shape[-1]
    return jax.lax.bitcast_convert_type(
        chunks.reshape(lead + (r, c // (4 * LANE), LANE, 4)), jnp.uint32)


def _run_matrix_packed(words: jax.Array, matrix_t, eng: str) -> jax.Array:
    """Execute ONE single-device tier on a packed-layout array (the
    dispatch body of apply_matrix_packed_best, shared with the mesh
    tier's per-shard callable)."""
    from . import xla_ops
    if eng == "xor":
        sched = _xor_sched_static(matrix_t)
        if use_pallas() and pallas_matrix_packed_supported(words.shape):
            return apply_matrix_xor_packed(words, sched,
                                           row_tile_cap=
                                           tuned_row_tile_cap(True))
        return apply_matrix_xor_xla_packed(words, sched)
    if eng == "mxu":
        out = xla_ops.apply_matrix_mxu(_packed_to_bytes(words), matrix_t)
        return _bytes_to_packed(out)
    if eng == "pallas":
        return apply_matrix_pallas_packed(words, matrix_t,
                                          row_tile_cap=
                                          tuned_row_tile_cap(True))
    out = xla_ops.apply_matrix_xla(_packed_to_bytes(words), matrix_t, 8)
    return _bytes_to_packed(out)


def _host_apply_bytes(chunks, matrix_t):
    """Numpy ground-truth twin of the w=8 byte-layout dispatch (the
    supervised plane's demoted-completion / self-verify reference)."""
    from .xor_schedule import host_matrix_apply
    arr = np.asarray(chunks)
    return host_matrix_apply(arr, np.asarray(matrix_t),
                             matrix_static=tuple(matrix_t), w=8)


def _host_apply_packed(words, matrix_t):
    """Packed-layout twin: packed words -> bytes (the numpy mirror of
    ``_packed_to_bytes``'s little-endian bitcast), the byte twin, and
    back — byte-identical to every device branch."""
    arr = np.ascontiguousarray(np.asarray(words))
    lead, (s, rows, lanes) = arr.shape[:-3], arr.shape[-3:]
    byts = arr.view(np.uint8).reshape(lead + (s, rows * lanes * 4))
    out = _host_apply_bytes(byts, matrix_t)
    r = out.shape[-2]
    return np.ascontiguousarray(out).reshape(
        lead + (r, rows, lanes * 4)).view(np.uint32)


def _supervised_matrix_dispatch(seam: str, x, matrix_t, w: int,
                                packed: bool, mesh, eng: str):
    """Route one eager matrix dispatch through the supervised plane
    (ops/supervisor.py).  ``rebuild`` re-runs engine selection, so a
    live tier demotion or plane reshrink lands the retried dispatch
    on the demoted tier; the numpy twin completes at the floor."""
    from .supervisor import global_supervisor

    def body(v, _eng=eng):
        if _eng == "numpy":
            return (_host_apply_packed(v, matrix_t) if packed
                    else _host_apply_bytes(v, matrix_t))
        if _eng == "mesh":
            return _apply_matrix_mesh(v, matrix_t, w, packed, mesh)
        if packed:
            return _run_matrix_packed(v, matrix_t, _eng)
        return _run_matrix_bytes(v, matrix_t, w, _eng)

    def rebuild():
        eng2 = select_matrix_engine(x.shape, matrix_t, w,
                                    packed=packed, mesh=mesh)
        return lambda v: body(v, eng2)

    host_fn = None
    if w == 8:
        host_fn = (lambda v: _host_apply_packed(v, matrix_t)) \
            if packed else (lambda v: _host_apply_bytes(v, matrix_t))
    return global_supervisor().dispatch(
        seam, body, (x,), host_fn=host_fn, rebuild=rebuild)


def apply_matrix_packed_best(words: jax.Array, matrix_t,
                             mesh=None) -> jax.Array:
    """Packed-layout dispatch through the selection table
    (select_matrix_engine / docs/PERF.md): the mesh tier when a data
    plane is active (stripe-batch axis sharded over the mesh, the
    single-device tier running per shard), MXU for large composite
    matrices, the generalized Pallas packed kernel otherwise on TPU;
    on other backends, bitcast to bytes and take the XLA path (CPU has
    no tiled layouts, so the casts are cheap there).  Byte-identical
    in every branch.

    Eager calls (concrete array in — a real dispatch, not a trace)
    record into the ``ops_apply_matrix_*`` telemetry histogram with
    the chosen engine tier as a label; traced calls record nothing,
    so jitted programs stay telemetry-free (docs/OBSERVABILITY.md)."""
    from ..telemetry.metrics import record_dispatch
    eng = select_matrix_engine(words.shape, matrix_t, 8, packed=True,
                               mesh=mesh)
    eager = not isinstance(words, jax.core.Tracer)
    with record_dispatch("ops_apply_matrix", eager=eager,
                         engine=eng, layout="packed"):
        if eager:
            return _supervised_matrix_dispatch(
                "ops.apply_matrix_packed", words, matrix_t, 8, True,
                mesh, eng)
        if eng == "mesh":
            return _apply_matrix_mesh(words, matrix_t, 8, True, mesh)
        return _run_matrix_packed(words, matrix_t, eng)


def _bitmatrix_kernel(rows_masks, s: int, w: int, r: int, rt: int):
    """Kernel body for a static (r*w, s*w) GF(2) bitmatrix in jerasure
    packet layout: out packet (i, l) = XOR of in packets (j, lb) whose
    bit is set.  Pure uint8 XOR — no word packing needed.  Blocks carry
    one (s, w*rt, LANE) packet-group tile per grid step; packet lb
    occupies sublane rows [lb*rt, (lb+1)*rt)."""

    def kernel(in_ref, out_ref):
        zero = None
        for row_idx, mask in enumerate(rows_masks):
            i, l = divmod(row_idx, w)
            acc = None
            col = 0
            m = mask
            while m:
                if m & 1:
                    j, lb = divmod(col, w)
                    p = in_ref[0, j, 0, lb * rt:(lb + 1) * rt, :]
                    acc = p if acc is None else acc ^ p
                m >>= 1
                col += 1
            if acc is None:
                if zero is None:
                    zero = jnp.zeros((rt, LANE), jnp.uint8)
                acc = zero
            out_ref[0, i, 0, l * rt:(l + 1) * rt, :] = acc

    return kernel


def pallas_bitmatrix_supported(shape, w: int, packetsize: int) -> bool:
    """w*packetsize-aligned chunks whose packets span >= 4 uint8
    sublane rows (packetsize a multiple of 512 bytes, the gate the
    tests pin; smaller packets fall back to the XLA path)."""
    if len(shape) < 2 or packetsize % (4 * LANE) != 0:
        return False
    c = shape[-1]
    return c > 0 and c % (w * packetsize) == 0


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def apply_bitmatrix_pallas(chunks: jax.Array, bitmatrix_rows, w: int,
                           packetsize: int,
                           interpret: bool = False) -> jax.Array:
    """Packet-layout bitmatrix apply on device, VMEM-resident — the
    Pallas path for the bitmatrix techniques (cauchy_*, liberation,
    blaum_roth, liber8tion, shec).  Same contract as
    xla_ops.apply_bitmatrix_xla; caller gates on
    pallas_bitmatrix_supported."""
    s = chunks.shape[-2]
    c = chunks.shape[-1]
    rw = len(bitmatrix_rows)
    r = rw // w
    lead = chunks.shape[:-2]
    b = int(np.prod(lead)) if lead else 1
    nb = c // (w * packetsize)
    rt = packetsize // LANE            # u8 rows per packet
    tiles = chunks.reshape(b, s, nb, w * rt, LANE)
    out = _pallas_call(
        _bitmatrix_kernel(bitmatrix_rows, s, w, r, rt),
        grid=(b, nb),
        in_specs=[pl.BlockSpec((1, s, 1, w * rt, LANE),
                               lambda i, j: (i, 0, j, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, r, 1, w * rt, LANE),
                               lambda i, j: (i, 0, j, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, r, nb, w * rt, LANE),
                                       jnp.uint8),
        interpret=interpret,
    )(tiles)
    return out.reshape(lead + (r, c))


# -- XOR-scheduled kernel family (ISSUE 12) ------------------------------
#
# The scheduler (ops/xor_schedule.py) turns a sparse/XOR-heavy
# composite matrix into a straight-line program of full-width SWAR ops
# (bit-matrix expansion -> greedy CSE, arxiv 2108.02692; polynomial-
# ring lazy reduction for monomial matrices, arxiv 1701.07731).  The
# kernels below EXECUTE that schedule: a Pallas variant per layout
# (byte + packed resident words) and an XLA fallback built from the
# same op list, all byte-identical to the dense kernels and to the
# numpy tier (xor_schedule.apply_schedule_numpy runs the identical
# schedule).  Scheduled programs are mul-free and gather-free by
# construction — tpu-audit pins them to the XOR-only allowlist
# (analysis/entrypoints.py GF_XOR_PRIMS).

def _xor_matrix_kernel(sched_static, s: int, r: int, pack, unpack):
    """Kernel body executing one XOR schedule over a (s, rt, LANE)
    block: pack every input chunk to SWAR words in registers, run the
    scheduled op list, unpack the output rows."""
    from .xor_schedule import eval_schedule

    def kernel(in_ref, out_ref):
        ins = [pack(in_ref[0, j]) for j in range(s)]
        outs = eval_schedule(sched_static, ins,
                             lambda: jnp.zeros_like(ins[0]))
        for i in range(r):
            out_ref[0, i] = unpack(outs[i])

    return kernel


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def apply_matrix_xor_pallas(chunks: jax.Array, sched_static,
                            interpret: bool = False,
                            row_tile_cap: int | None = None
                            ) -> jax.Array:
    """Byte-layout XOR-scheduled apply: (..., s, C) uint8 ->
    (..., r, C), same contract (and same pad-and-mask row tiling,
    same static autotuned ``row_tile_cap`` seam) as
    apply_matrix_pallas; the matrix is baked into ``sched_static``
    (xor_schedule.XorSchedule.static)."""
    _, s, r, _, _ = sched_static
    assert chunks.shape[-2] == s and chunks.dtype == jnp.uint8
    lead = chunks.shape[:-2]
    c = chunks.shape[-1]
    rows = c // LANE
    b = int(np.prod(lead)) if lead else 1
    tiles = chunks.reshape(b, s, rows, LANE)
    pad = (-rows) % SUBLANE_U8
    if pad:
        tiles = jnp.pad(tiles, ((0, 0), (0, 0), (0, pad), (0, 0)))
    prows = rows + pad
    rt = _row_tile8(prows, row_tile_cap)
    out = _pallas_call(
        _xor_matrix_kernel(sched_static, s, r,
                           lambda v: _pack_words(v, interpret),
                           lambda v: _unpack_words(v, interpret)),
        grid=(b, prows // rt),
        in_specs=[pl.BlockSpec((1, s, rt, LANE),
                               lambda i, j: (i, 0, j, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, r, rt, LANE),
                               lambda i, j: (i, 0, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, r, prows, LANE), jnp.uint8),
        interpret=interpret,
    )(tiles)
    if pad:
        out = out[..., :rows, :]
    return out.reshape(lead + (r, c))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def apply_matrix_xor_packed(words: jax.Array, sched_static,
                            interpret: bool = False,
                            row_tile_cap: int | None = None
                            ) -> jax.Array:
    """Packed-layout XOR-scheduled apply: (..., s, R, 128) uint32 ->
    (..., r, R, 128) — the resident-word twin of
    apply_matrix_pallas_packed (identity register pack, arbitrary row
    counts via zero-pad + masked writeback, same static autotuned
    ``row_tile_cap`` seam)."""
    _, s, r, _, _ = sched_static
    assert words.shape[-3] == s and words.dtype == jnp.uint32
    assert words.shape[-1] == LANE
    lead = words.shape[:-3]
    rows = words.shape[-2]
    b = int(np.prod(lead)) if lead else 1
    tiles = words.reshape(b, s, rows, LANE)
    pad = (-rows) % SUBLANE_U32
    if pad:
        tiles = jnp.pad(tiles, ((0, 0), (0, 0), (0, pad), (0, 0)))
    prows = rows + pad
    rt = _row_tile8(prows * 4, row_tile_cap) // 4
    if rt == 0 or prows % rt:
        rt = prows
    ident = lambda v: v  # noqa: E731
    out = _pallas_call(
        _xor_matrix_kernel(sched_static, s, r, ident, ident),
        grid=(b, prows // rt),
        in_specs=[pl.BlockSpec((1, s, rt, LANE),
                               lambda i, j: (i, 0, j, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, r, rt, LANE),
                               lambda i, j: (i, 0, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, r, prows, LANE), jnp.uint32),
        interpret=interpret,
    )(tiles)
    if pad:
        out = out[..., :rows, :]
    return out.reshape(lead + (r, rows, LANE))


@functools.partial(jax.jit, static_argnums=(1,))
def apply_matrix_xor_xla(chunks: jax.Array, sched_static) -> jax.Array:
    """The XLA fallback built from the same schedule: (..., s, C)
    uint8 (C % 4 == 0) -> (..., r, C).  Byte-identical to the Pallas
    variant and the numpy tier by construction (one op list)."""
    from .xor_schedule import eval_schedule

    _, s, r, _, _ = sched_static
    assert chunks.shape[-2] == s and chunks.dtype == jnp.uint8
    c = chunks.shape[-1]
    assert c % 4 == 0, c
    words = jax.lax.bitcast_convert_type(
        chunks.reshape(chunks.shape[:-1] + (c // 4, 4)), jnp.uint32)
    ins = [words[..., j, :] for j in range(s)]
    outs = eval_schedule(sched_static, ins,
                         lambda: jnp.zeros_like(ins[0]))
    out = jnp.stack(outs, axis=-2)
    out = jax.lax.bitcast_convert_type(out, jnp.uint8)
    return out.reshape(out.shape[:-2] + (c,))


@functools.partial(jax.jit, static_argnums=(1,))
def apply_matrix_xor_xla_packed(words: jax.Array,
                                sched_static) -> jax.Array:
    """Packed-layout XLA build of the schedule: (..., s, R, 128)
    uint32 -> (..., r, R, 128), zero layout work."""
    from .xor_schedule import eval_schedule

    _, s, r, _, _ = sched_static
    assert words.shape[-3] == s and words.dtype == jnp.uint32
    ins = [words[..., j, :, :] for j in range(s)]
    outs = eval_schedule(sched_static, ins,
                         lambda: jnp.zeros_like(ins[0]))
    return jnp.stack(outs, axis=-3)


def _xor_sched_static(matrix_t):
    """The schedule the selection table routed ``matrix_t`` to (the
    probe is lru-cached, so this is a dict hit on the dispatch path).
    A tuned engine PIN (ISSUE 14) may route to the xor tier past the
    cutover heuristic — measurement beat the model — so when the
    preference probe declines, fall through to the raw schedule."""
    from .xor_schedule import preferred_schedule, probe_schedule
    sched = preferred_schedule(matrix_t, 8, mxu_min=mxu_matrix_min())
    if sched is None:
        sched = probe_schedule(matrix_t, 8)
    assert sched is not None, "xor tier selected without a schedule"
    return sched.static


# -- scheduled bitmatrix (packet layout) ---------------------------------

def _bitmatrix_xor_kernel(sched_static, s: int, w: int, r: int,
                          rt: int):
    """Packet-layout schedule body: inputs are the s*w packets of one
    block, ops are pure XOR (CSE temps), outputs the r*w parity
    packets."""
    from .xor_schedule import eval_schedule_u8

    n_in = sched_static[1]

    def kernel(in_ref, out_ref):
        ins = []
        for idx in range(n_in):
            j, lb = divmod(idx, w)
            ins.append(in_ref[0, j, 0, lb * rt:(lb + 1) * rt, :])
        outs = eval_schedule_u8(
            sched_static, ins,
            lambda: jnp.zeros((rt, LANE), jnp.uint8))
        for row_idx in range(r * w):
            i, l = divmod(row_idx, w)
            out_ref[0, i, 0, l * rt:(l + 1) * rt, :] = outs[row_idx]

    return kernel


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def apply_bitmatrix_xor_pallas(chunks: jax.Array, sched_static,
                               w: int, packetsize: int,
                               interpret: bool = False) -> jax.Array:
    """XOR-scheduled packet-layout bitmatrix apply — the CSE'd twin
    of apply_bitmatrix_pallas (same tiling gate:
    pallas_bitmatrix_supported)."""
    s = chunks.shape[-2]
    c = chunks.shape[-1]
    rw = sched_static[2]
    r = rw // w
    lead = chunks.shape[:-2]
    b = int(np.prod(lead)) if lead else 1
    nb = c // (w * packetsize)
    rt = packetsize // LANE
    tiles = chunks.reshape(b, s, nb, w * rt, LANE)
    out = _pallas_call(
        _bitmatrix_xor_kernel(sched_static, s, w, r, rt),
        grid=(b, nb),
        in_specs=[pl.BlockSpec((1, s, 1, w * rt, LANE),
                               lambda i, j: (i, 0, j, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, r, 1, w * rt, LANE),
                               lambda i, j: (i, 0, j, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, r, nb, w * rt, LANE),
                                       jnp.uint8),
        interpret=interpret,
    )(tiles)
    return out.reshape(lead + (r, c))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def apply_bitmatrix_xor_xla(chunks: jax.Array, sched_static, w: int,
                            packetsize: int) -> jax.Array:
    """XLA build of a packet-layout bitmatrix schedule (same packet
    assembly as xla_ops.apply_bitmatrix_xla, CSE temps shared)."""
    from .xor_schedule import eval_schedule_u8

    s = chunks.shape[-2]
    c = chunks.shape[-1]
    rw = sched_static[2]
    r = rw // w
    assert c % (w * packetsize) == 0, (c, w, packetsize)
    nb = c // (w * packetsize)
    dv = chunks.reshape(chunks.shape[:-2] + (s, nb, w, packetsize))
    n_in = sched_static[1]
    ins = []
    for idx in range(n_in):
        j, lb = divmod(idx, w)
        ins.append(dv[..., j, :, lb, :])
    outs = eval_schedule_u8(
        sched_static, ins,
        lambda: jnp.zeros(chunks.shape[:-2] + (nb, packetsize),
                          jnp.uint8))
    stacked = jnp.stack(outs, axis=-3)          # (..., rw, nb, p)
    stacked = stacked.reshape(stacked.shape[:-3]
                              + (r, w, nb, packetsize))
    stacked = jnp.swapaxes(stacked, -3, -2)     # (..., r, nb, w, p)
    return stacked.reshape(stacked.shape[:-4] + (r, c))


def _device_kind() -> str:
    """Probed default-backend kind, via the explicit fallback policy
    (ops/fallback.py — specific exception types only, no silent
    swallowing; "none" means no XLA backend initializes).  Kept as a
    module-level function so tests can pin the device kind."""
    from .fallback import global_policy
    return global_policy().device_kind()


def use_pallas() -> bool:
    """The kernel lowers through Mosaic for TPU backends only; every
    other backend — cpu, gpu — takes the XLA path (interpreter mode
    is for tests).
    Routed through the fallback policy, which logs the selected
    engine once per outcome."""
    from .fallback import global_policy
    return global_policy().engine(_device_kind()) == "pallas"


# NONZERO-entry count above which a GF(2^8) matrix routes to the MXU
# matmul path on TPU: the unrolled xtime/XOR schedule's op count and
# HBM traffic scale with set bits, not dimensions (XLA dead-code
# eliminates planes no entry uses), so a huge-but-nearly-empty matrix
# stays on the near-memcpy schedule while composite matrices (clay's
# 64x704 single-erasure decode, ~2.2k nonzeros) become one MXU
# contraction (ops/xla_ops.py -> apply_matrix_mxu)
MXU_MATRIX_MIN = 2048


def mxu_matrix_min() -> int:
    """The MXU nonzero cutover: the tuned value from the installed
    best-config table (kind ``engine-select``), else MXU_MATRIX_MIN —
    the autotuner's threshold consultation seam (ISSUE 14).  Every
    tier is byte-identical, so a tuned cutover moves only WHERE the
    product runs."""
    from ..tune.table import consult
    cfg = consult("engine-select")
    if cfg:
        v = cfg.get("mxu_matrix_min")
        if isinstance(v, int) and not isinstance(v, bool) and v > 0:
            return v
    return MXU_MATRIX_MIN


@functools.lru_cache(maxsize=256)
def _matrix_nnz(matrix_t) -> int:
    # cached: matrix_t is the hashable static tuple, and this runs in
    # the per-call dispatch path (45k Python iterations for a clay
    # composite would otherwise tax every apply)
    return sum(1 for row in matrix_t for v in row if v)


def _resolve_mesh(mesh):
    """Resolve the ``mesh`` argument of the dispatchers: None -> the
    active data plane (parallel/plane.py; None when none is active or
    the call is inside a sharded program body), a DataPlane/Mesh ->
    itself, falsy -> mesh tier disabled."""
    from ..parallel.plane import resolve_plane
    return resolve_plane(mesh)


def _tuned_engine_pin(shape, matrix_t, w: int, packed: bool,
                      engine: str) -> str | None:
    """The autotuner's per-matrix tier pin (ISSUE 14): the measured
    winner for this static matrix from the best-config table (kind
    ``matrix-engine``, profile slot ``m:<digest>``), VALIDATED against
    what this shape/backend can actually dispatch — an undispatchable
    pin falls back to the heuristic table byte-identically, it never
    errors.  Every tier computes identical bytes, so a pin moves only
    where the product runs."""
    if w != 8 or not matrix_t:
        return None
    from ..tune.table import consult, matrix_digest
    cfg = consult("matrix-engine",
                  profile="m:" + matrix_digest(matrix_t),
                  layout="packed" if packed else "bytes",
                  device_count=1)
    if cfg is None:
        # most pins are written under the bytes layout; a packed
        # dispatch of the same matrix runs the same tier
        cfg = consult("matrix-engine",
                      profile="m:" + matrix_digest(matrix_t),
                      layout="bytes", device_count=1)
    if not cfg:
        return None
    pin = cfg.get("engine")
    if pin == "xla":
        return "xla"
    if pin == "xor":
        from .xor_schedule import probe_schedule
        ok = (packed or (len(shape) >= 2 and shape[-1] % 4 == 0)) \
            and probe_schedule(matrix_t, 8) is not None
        return "xor" if ok else None
    if engine != "pallas":
        return None          # mxu/pallas pins need the TPU tier live
    if pin == "mxu":
        return "mxu"
    if pin == "pallas":
        sup = (pallas_matrix_packed_supported(shape) if packed
               else pallas_matrix_padded_supported(shape, 8))
        return "pallas" if sup else None
    return None


def select_matrix_engine(shape, matrix_t, w: int = 8,
                         packed: bool = False,
                         engine: str | None = None,
                         mesh=None) -> str:
    """THE engine-selection table for GF(2^w) matrix applies — one
    place that decides, for a (shape, matrix, layout) triple, which
    compute tier runs it (docs/PERF.md has the human-readable table;
    ops/fallback.py supplies the device tier).  Returns one of:

    - "mesh":   a data plane is active (parallel/plane.py) and the
                shape carries a shardable stripe-batch axis — the
                apply runs under shard_map with the batch sharded
                over the mesh and the matrix replicated, the
                single-device tier below executing per shard.
    - "xor":    w=8 matrix whose XOR schedule (ops/xor_schedule.py:
                bit-matrix expansion + greedy CSE, ring transform for
                monomial matrices) beats the dense-multiply cost
                model — the scheduled kernel family runs it (Pallas
                on TPU, the XLA build elsewhere; shec plan matrices,
                lrc probed composites, parity-only patterns).
    - "mxu":    w=8 composite matrix with >= MXU_MATRIX_MIN nonzeros
                on a Pallas-capable backend — the bit-sliced GF(2)
                matmul (clay's 64x704 single-erasure composite) —
                unless the XOR schedule undercuts it.
    - "pallas": the bit-sliced VPU kernel (byte, padded-byte, packed,
                or word variant per layout/w) on a TPU backend.
    - "xla":    the SWAR XLA path (non-TPU backends, or shapes no
                Pallas variant supports).
    - "numpy":  the fallback policy dropped to the host ground truth;
                callers must not dispatch through jax at all.  The
                mesh tier NEVER overrides this — a plane cannot make
                a dead backend live, so it degrades here exactly like
                the single-device table (never silently to host).

    ``engine`` overrides the probed fallback-policy tier and ``mesh``
    the active data plane (tests).  Pure function of its arguments
    plus the three process policies (fallback tier, data plane, and
    the installed best-config table — a tuned per-matrix pin or
    cutover threshold reroutes here, ISSUE 14) — the routing tests
    assert on it directly."""
    if engine is None:
        from .fallback import global_policy
        engine = global_policy().engine(_device_kind())
    if engine == "numpy":
        return "numpy"
    plane = _resolve_mesh(mesh)
    if (plane is not None and plane.n_devices > 1
            and len(shape) >= (4 if packed else 3) and shape[0] >= 2):
        return "mesh"
    # the autotuner's per-matrix pin (ISSUE 14): a measured winner in
    # the installed best-config table overrides the heuristics below
    # — validated as dispatchable, byte-identical by construction,
    # and consulted AFTER the numpy/mesh topology decisions (a pin
    # can choose a kernel, never resurrect a dead backend or unshard
    # a plane)
    pin = _tuned_engine_pin(shape, matrix_t, w, packed, engine)
    if pin is not None:
        return pin
    # the XOR-density probe (ops/xor_schedule.py): a schedulable w=8
    # matrix whose scheduled op count beats the dense-multiply model
    # runs the scheduled kernel family on BOTH device tiers (Pallas on
    # TPU, the XLA build of the same schedule elsewhere); the cutover
    # thresholds are themselves tuned-table seams (mxu_matrix_min,
    # tuned_xor_cutover)
    if (w == 8 and matrix_t
            and (packed or (len(shape) >= 2 and shape[-1] % 4 == 0))):
        from .xor_schedule import preferred_schedule
        if preferred_schedule(matrix_t, 8,
                              mxu_min=mxu_matrix_min()) is not None:
            return "xor"
    if engine != "pallas":
        return "xla"
    nnz = _matrix_nnz(matrix_t) if matrix_t else 0
    if w == 8 and nnz >= mxu_matrix_min():
        return "mxu"
    if packed:
        return "pallas" if pallas_matrix_packed_supported(shape) else "xla"
    if w == 8:
        return ("pallas" if pallas_matrix_padded_supported(shape, w)
                else "xla")
    if w in (16, 32):
        return ("pallas" if pallas_matrix_words_supported(shape, w)
                else "xla")
    return "xla"


def _run_matrix_bytes(chunks: jax.Array, matrix_t, w: int,
                      eng: str) -> jax.Array:
    """Execute ONE single-device tier on a byte/word-layout array (the
    dispatch body of apply_matrix_best, shared with the mesh tier's
    per-shard callable)."""
    from . import xla_ops
    from .xla_ops import apply_matrix_xla
    if eng == "xor":
        sched = _xor_sched_static(matrix_t)
        if use_pallas() and pallas_matrix_padded_supported(chunks.shape,
                                                          8):
            return apply_matrix_xor_pallas(chunks, sched,
                                           row_tile_cap=
                                           tuned_row_tile_cap(False))
        return apply_matrix_xor_xla(chunks, sched)
    if eng == "mxu":
        # module attribute (not a local import) so the routing test
        # can observe which engine was selected
        return xla_ops.apply_matrix_mxu(chunks, matrix_t)
    if eng == "pallas":
        if w == 8:
            return apply_matrix_pallas(chunks, matrix_t,
                                       row_tile_cap=
                                       tuned_row_tile_cap(False))
        return apply_matrix_pallas_words(chunks, matrix_t, w)
    return apply_matrix_xla(chunks, matrix_t, w)


@functools.lru_cache(maxsize=256)
def _mesh_apply_fn(mesh, axis: str, ndev: int, matrix_t, w: int,
                   packed: bool, inner: str, rank: int):
    """Compile-once cache of the mesh-tier program for one (mesh,
    matrix, layout, inner tier, rank): the single-device apply under
    shard_map with the stripe-batch axis sharded and the matrix a
    replicated trace-time constant.  Non-dividing batches are
    zero-padded up to the device count and the pad rows masked off the
    output (GF region math is row-local, so pad stripes never mix into
    real rows — the same argument as the packed kernels' row
    padding).  jit caches per input shape on the returned wrapper, so
    repeat batches re-trace nothing."""
    from ..utils.shard import batch_spec, shard_map_compat

    spec = batch_spec(axis, rank)

    def body(local):
        if packed:
            return _run_matrix_packed(local, matrix_t, inner)
        return _run_matrix_bytes(local, matrix_t, w, inner)

    sharded = shard_map_compat(body, mesh, in_specs=spec, out_specs=spec)

    @jax.jit
    def fn(x):
        b = x.shape[0]
        pad = (-b) % ndev
        if pad:
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        out = sharded(x)
        return out[:b] if pad else out

    return fn


def _apply_matrix_mesh(x: jax.Array, matrix_t, w: int, packed: bool,
                       mesh) -> jax.Array:
    """The mesh tier: ONE sharded device dispatch over the active data
    plane, byte-identical to the single-device tier by construction
    (per-shard math is that tier verbatim; stripes are independent)."""
    plane = _resolve_mesh(mesh)
    # the per-shard tier, selected on the local shard shape with the
    # mesh disabled (batch size never changes the support gates)
    inner = select_matrix_engine((1,) + tuple(x.shape[1:]), matrix_t,
                                 w, packed=packed, mesh=0)
    if plane is None:
        # the plane was deactivated between selection and dispatch:
        # degrade to the single-device tier (never to host)
        if packed:
            return _run_matrix_packed(x, matrix_t, inner)
        return _run_matrix_bytes(x, matrix_t, w, inner)
    if not isinstance(x, jax.core.Tracer):
        from ..telemetry import metrics as tel
        tel.counter("engine_mesh_dispatches",
                    tier=f"apply-{'packed' if packed else 'bytes'}",
                    devices=str(plane.n_devices))
        if (isinstance(x, jax.Array) and x.committed
                and x.sharding.device_set != set(plane.mesh.devices.flat)):
            # a batch committed elsewhere (e.g. resident on one chip)
            # is moved onto the plane explicitly: jit refuses a
            # committed argument from another device set
            from jax.sharding import NamedSharding, PartitionSpec
            from ..utils.shard import batch_spec
            spec = (batch_spec(plane.axis, x.ndim)
                    if x.shape[0] % plane.n_devices == 0
                    else PartitionSpec())
            x = jax.device_put(x, NamedSharding(plane.mesh, spec))
    fn = _mesh_apply_fn(plane.mesh, plane.axis, plane.n_devices,
                        matrix_t, w, packed, inner, x.ndim)
    return fn(x)


def apply_matrix_best(chunks: jax.Array, matrix_t, w: int = 8,
                      mesh=None) -> jax.Array:
    """Dispatch over the engines via select_matrix_engine,
    byte-identical in every branch (cross-pinned in tests):

    - active data plane (parallel/plane.py) + a stripe-batched shape:
      the mesh tier — the per-shard tier below under shard_map, batch
      axis sharded, matrix replicated, one device dispatch.
    - w=8, LARGE matrix (>= MXU_MATRIX_MIN entries) on TPU: the
      bit-sliced GF(2) matmul on the MXU (clay composites).
    - w=8, uint8 in: the byte Pallas kernel on TPU (row counts off the
      sublane tile pad + mask — the composite generalization), XLA
      otherwise.
    - w=16/32, word-typed in (uint16/uint32 views — what the plugin
      mixins pass): the word Pallas kernel on TPU, XLA otherwise.
    """
    from ..telemetry.metrics import record_dispatch
    word_typed = ((w == 8 and chunks.dtype == jnp.uint8)
                  or (w in (16, 32) and chunks.dtype == _WORD_DTYPE.get(w)))
    eng = (select_matrix_engine(chunks.shape, matrix_t, w, mesh=mesh)
           if word_typed else "xla")
    eager = not isinstance(chunks, jax.core.Tracer)
    with record_dispatch("ops_apply_matrix", eager=eager,
                         engine=eng, layout="bytes"):
        if eager:
            return _supervised_matrix_dispatch(
                "ops.apply_matrix", chunks, matrix_t, w, False, mesh,
                eng)
        if eng == "mesh":
            return _apply_matrix_mesh(chunks, matrix_t, w, False, mesh)
        return _run_matrix_bytes(chunks, matrix_t, w, eng)


def apply_bitmatrix_best(chunks: jax.Array, bitmatrix_rows, w: int,
                         packetsize: int) -> jax.Array:
    """Dispatch for packet-layout bitmatrix codes: the CSE-scheduled
    kernel when the greedy sharing pays (ops/xor_schedule.py ::
    probe_bitmatrix_schedule — jerasure's smart-scheduling analog),
    the plain packet kernel otherwise; Pallas on TPU when the packets
    tile, XLA elsewhere.  Byte-identical in every branch."""
    from .xla_ops import apply_bitmatrix_xla
    from .xor_schedule import probe_bitmatrix_schedule
    sched = probe_bitmatrix_schedule(tuple(bitmatrix_rows), w)
    if (use_pallas()
            and pallas_bitmatrix_supported(chunks.shape, w, packetsize)):
        if sched is not None:
            return apply_bitmatrix_xor_pallas(chunks, sched.static, w,
                                              packetsize)
        return apply_bitmatrix_pallas(chunks, bitmatrix_rows, w,
                                      packetsize)
    if sched is not None:
        return apply_bitmatrix_xor_xla(chunks, sched.static, w,
                                       packetsize)
    return apply_bitmatrix_xla(chunks, bitmatrix_rows, w, packetsize)


# -- operand-coefficient family (pattern-generic decode) -----------------
#
# Every kernel above bakes its matrix into the trace, so a decode
# program exists per erasure pattern.  The family below takes the
# coefficients as an OPERAND instead: one (r, s) uint8 block per
# stripe of the batch, so stripes of different erasure patterns (same
# r, same s) share one compiled program and one dispatch.  The body
# walks the xtime doubling ladder of each input tile once and, for
# every output row, folds plane t in under a mask made from bit t of
# that stripe's coefficient — the static kernel's schedule with the
# "does bit t of matrix[i][j] exist" decision moved from trace time to
# a scalar read.

OPERAND_SCOPE = "gf_operand_apply"


def _gf8_operand_kernel(s: int, r: int, interpret: bool):
    """w=8 operand-coefficient body.  Index 0 of the kernel args is the
    scalar-prefetch ref of the flattened (B, r, s) int32 coefficients;
    grid axis 0 walks the stripes."""

    def kernel(coef_ref, in_ref, out_ref):
        base = pl.program_id(0) * (r * s)
        accs = [None] * r
        for j in range(s):
            plane = _pack_words(in_ref[0, j], interpret)
            col = [coef_ref[base + i * s + j] for i in range(r)]
            for t in range(8):
                if t > 0:
                    plane = _xtime_swar(plane, 8)
                for i in range(r):
                    # all ones where bit t of the coefficient is set
                    mask = (-((col[i] >> t) & 1)).astype(jnp.uint32)
                    term = plane & mask
                    accs[i] = term if accs[i] is None else accs[i] ^ term
        for i in range(r):
            out_ref[0, i] = _unpack_words(accs[i], interpret)

    return kernel


@functools.partial(jax.jit, static_argnums=(2, 3))
def apply_matrix_operand_pallas(chunks: jax.Array, coef: jax.Array,
                                interpret: bool = False,
                                row_tile_cap: int | None = None
                                ) -> jax.Array:
    """Apply a per-stripe (r, s) GF(2^8) matrix to (B, s, C) uint8
    chunks -> (B, r, C): ``out[b, i] = XOR_j coef[b, i, j] * chunks[b,
    j]``.  ``coef`` (B, r, s) uint8 is a TRACED operand (scalar
    prefetch), so one compiled program serves every matrix of that
    shape; for a batch that repeats one matrix the bytes equal
    :func:`apply_matrix_pallas`'s.  Caller gates on
    pallas_matrix_padded_supported."""
    b, s, c = chunks.shape
    r = coef.shape[1]
    assert coef.shape == (b, r, s) and chunks.dtype == jnp.uint8
    rows = c // LANE
    tiles = chunks.reshape(b, s, rows, LANE)
    pad = (-rows) % SUBLANE_U8
    if pad:
        tiles = jnp.pad(tiles, ((0, 0), (0, 0), (0, pad), (0, 0)))
    prows = rows + pad
    rt = _row_tile8(prows, row_tile_cap)
    with jax.named_scope(OPERAND_SCOPE):
        out = _pallas_call(
            _gf8_operand_kernel(s, r, interpret),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, prows // rt),
                in_specs=[pl.BlockSpec((1, s, rt, LANE),
                                       lambda i, j, cf: (i, 0, j, 0))],
                out_specs=pl.BlockSpec((1, r, rt, LANE),
                                       lambda i, j, cf: (i, 0, j, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((b, r, prows, LANE),
                                           jnp.uint8),
            interpret=interpret,
        )(coef.astype(jnp.int32).reshape(-1), tiles)
    if pad:
        out = out[..., :rows, :]
    return out.reshape(b, r, c)


def _xtime8_bytes(v: jax.Array) -> jax.Array:
    """xtime on uint8 elements (one field byte each)."""
    hi = v >> jnp.uint8(7)
    return (v << jnp.uint8(1)) ^ (hi * jnp.uint8(0x1D))


@jax.jit
def apply_matrix_operand_xla(chunks: jax.Array,
                             coef: jax.Array) -> jax.Array:
    """The XLA tier of :func:`apply_matrix_operand_pallas` (backends
    without Mosaic, chunk sizes off the lane width): the same ladder
    and masks on uint8 elements, byte-identical."""
    b, s, c = chunks.shape
    r = coef.shape[1]
    with jax.named_scope(OPERAND_SCOPE):
        acc = jnp.zeros((b, r, c), jnp.uint8)
        for j in range(s):
            plane = chunks[:, j, None, :]
            for t in range(8):
                if t > 0:
                    plane = _xtime8_bytes(plane)
                mask = jnp.uint8(0) - ((coef[:, :, j] >> t) & 1)
                acc = acc ^ (plane & mask[:, :, None])
    return acc


def host_apply_operand(chunks, coef) -> np.ndarray:
    """Numpy ground truth of the operand family: one product-table
    gather per (output row, input chunk)."""
    from ..gf.gf8 import gf8
    mul = gf8().mul_table
    x = np.asarray(chunks)
    cf = np.asarray(coef)
    b, s, c = x.shape
    r = cf.shape[1]
    out = np.zeros((b, r, c), np.uint8)
    for i in range(r):
        for j in range(s):
            out[:, i] ^= mul[cf[:, i, j, None], x[:, j]]
    return out


def apply_matrix_operand_best(chunks: jax.Array,
                              coef: jax.Array) -> jax.Array:
    """Per-stripe-coefficient apply on the device tier: the Pallas
    kernel on TPU where the chunk size tiles, the XLA tier otherwise.
    Byte-identical either way; the serve seam that calls it
    (codes/engine.py::serve_decode_generic) supervises the dispatch
    and carries the numpy twin."""
    if use_pallas() and pallas_matrix_padded_supported(chunks.shape, 8):
        return apply_matrix_operand_pallas(
            chunks, coef, row_tile_cap=tuned_row_tile_cap(False))
    return apply_matrix_operand_xla(chunks, coef)
