"""AOT compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide §2): each test
lowers one kernel family the main path can route to, at real widths
(the north-star 64 stripes x k=8 x 128 KiB, clay's 64x704 composite,
512 x 64 KiB pages, the chip smoke's CRUSH pools), and compiles it —
raising whatever the chip's compiler would raise (VMEM over budget,
unaligned slices, a program that does not fit HBM).  Nothing runs:
this is a compile rehearsal, never a chip result.

The topology is described only inside the module fixture below (never
at import, in ``skipif`` or in ``parametrize``): one process at a time
may load the TPU library, and every xdist worker imports this file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

# the autotuner's row-tile space (tune/space.py "row-tile"): the
# default cap and the largest legal one
ROW_TILE_CAPS = (512, 2048)
STRIPES, K, M, CHUNK = 64, 8, 3, 128 * 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(jitted, *args):
    compiled = jitted.lower(*args).compile()
    return compiled, compiled.as_text()


def _rs_matrix(w: int = 8):
    from ceph_tpu.matrices.jerasure import \
        reed_sol_vandermonde_coding_matrix
    from ceph_tpu.ops.xla_ops import matrix_to_static
    return matrix_to_static(reed_sol_vandermonde_coding_matrix(K, M, w))


def test_topology_is_a_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


@pytest.mark.parametrize("cap", ROW_TILE_CAPS)
def test_byte_kernel_compiles(one_chip, cap):
    from ceph_tpu.ops.pallas_gf import apply_matrix_pallas
    x = _sds(one_chip, (STRIPES, K, CHUNK), jnp.uint8)
    _, text = _compile(apply_matrix_pallas, x, _rs_matrix(), False, cap)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cap", ROW_TILE_CAPS)
def test_packed_kernel_compiles(one_chip, cap):
    from ceph_tpu.ops.pallas_gf import apply_matrix_pallas_packed
    x = _sds(one_chip, (STRIPES, K, CHUNK // 512, 128), jnp.uint32)
    _, text = _compile(apply_matrix_pallas_packed, x, _rs_matrix(),
                       False, cap)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("w", [16, 32])
def test_word_kernel_compiles(one_chip, w):
    from ceph_tpu.ops.pallas_gf import (_WORD_DTYPE,
                                        apply_matrix_pallas_words)
    x = _sds(one_chip, (STRIPES, K, CHUNK // (w // 8)), _WORD_DTYPE[w])
    _, text = _compile(apply_matrix_pallas_words, x, _rs_matrix(w), w)
    assert "tpu_custom_call" in text


def test_bitmatrix_kernels_compile(one_chip):
    """cauchy_good k=8 m=3, packetsize 2048: the plain packet kernel
    and the CSE-scheduled one apply_bitmatrix_best routes to."""
    from ceph_tpu.codes.registry import ErasureCodePluginRegistry
    from ceph_tpu.ops.pallas_gf import (apply_bitmatrix_pallas,
                                        apply_bitmatrix_xor_pallas)
    from ceph_tpu.ops.xor_schedule import probe_bitmatrix_schedule
    ec = ErasureCodePluginRegistry.instance().factory(
        "jerasure", {"technique": "cauchy_good", "k": str(K),
                     "m": str(M), "packetsize": "2048"})
    rows = ec._bitmatrix_static
    x = _sds(one_chip, (STRIPES, K, CHUNK), jnp.uint8)
    _, text = _compile(apply_bitmatrix_pallas, x, rows, ec.w, 2048)
    assert "tpu_custom_call" in text
    sched = probe_bitmatrix_schedule(tuple(rows), ec.w)
    assert sched is not None
    _, text = _compile(apply_bitmatrix_xor_pallas, x, sched.static,
                       ec.w, 2048)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cap", ROW_TILE_CAPS)
@pytest.mark.parametrize("layout", ["bytes", "packed"])
def test_xor_schedule_kernels_compile(one_chip, layout, cap):
    from ceph_tpu.ops.pallas_gf import (apply_matrix_xor_packed,
                                        apply_matrix_xor_pallas)
    from ceph_tpu.ops.xor_schedule import probe_schedule
    sched = probe_schedule(_rs_matrix(), 8)
    assert sched is not None
    if layout == "bytes":
        x = _sds(one_chip, (STRIPES, K, CHUNK), jnp.uint8)
        fn = apply_matrix_xor_pallas
    else:
        x = _sds(one_chip, (STRIPES, K, CHUNK // 512, 128), jnp.uint32)
        fn = apply_matrix_xor_packed
    _, text = _compile(fn, x, sched.static, False, cap)
    assert "tpu_custom_call" in text


def test_mxu_route_compiles_clay_composite(one_chip):
    """clay k=8 m=4 d=11 single-chunk repair: the 64x704 composite on
    (stripes, 704, 2048) sub-chunk rows, through the einsum route."""
    from ceph_tpu.codes.registry import ErasureCodePluginRegistry
    from ceph_tpu.ops.pallas_gf import MXU_MATRIX_MIN, _matrix_nnz
    from ceph_tpu.ops.xla_ops import apply_matrix_mxu
    clay = ErasureCodePluginRegistry.instance().factory(
        "clay", {"k": "8", "m": "4", "d": "11"})
    avail = tuple(range(1, 12))
    _, ms = clay._decode_composite(avail, (0,))
    assert (len(ms), len(ms[0])) == (64, 704)
    assert _matrix_nnz(ms) >= MXU_MATRIX_MIN
    sub = clay.sub_chunk_no
    x = _sds(one_chip, (STRIPES, 11 * sub, CHUNK // sub), jnp.uint8)
    compiled, text = _compile(apply_matrix_mxu, x, ms, 8)
    assert "dot" in text or "convolution" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


@pytest.mark.parametrize("cap", ROW_TILE_CAPS)
def test_ragged_kernel_compiles(one_chip, cap):
    from ceph_tpu.ops.pallas_gf import apply_matrix_pallas_ragged
    pool = _sds(one_chip, (512, K, 64 * 1024), jnp.uint8)
    mask = _sds(one_chip, (512,), jnp.uint8)
    _, text = _compile(apply_matrix_pallas_ragged, pool, _rs_matrix(),
                       mask, False, cap)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cap", ROW_TILE_CAPS)
@pytest.mark.parametrize("erased", [1, 2, 3])
def test_operand_kernel_compiles(one_chip, erased, cap):
    """The pattern-generic decode kernel at the served shape: a rung of
    64 stripes of k=8 survivors, 128 KiB chunks, per-stripe
    coefficients in SMEM."""
    from ceph_tpu.ops.pallas_gf import apply_matrix_operand_pallas
    x = _sds(one_chip, (STRIPES, K, CHUNK), jnp.uint8)
    coef = _sds(one_chip, (STRIPES, erased, K), jnp.uint8)
    _, text = _compile(apply_matrix_operand_pallas, x, coef, False, cap)
    assert "tpu_custom_call" in text


def test_kernels_compile_with_x64_on(one_chip):
    """A Pallas kernel traced with jax_enable_x64 on (a caller inside
    crush/bulk.py's with_x64 scope, or one that set it globally) must
    still lower to 32-bit index maps: Mosaic refused 'func.return'
    before ops/pallas_gf.py applied every pallas_call with x64 off."""
    from ceph_tpu.ops.pallas_gf import (apply_matrix_pallas,
                                        apply_matrix_pallas_ragged)
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        x = _sds(one_chip, (STRIPES, K, CHUNK), jnp.uint8)
        _, text = _compile(apply_matrix_pallas, x, _rs_matrix(), False,
                           None)
        assert "tpu_custom_call" in text
        pool = _sds(one_chip, (512, K, 64 * 1024), jnp.uint8)
        mask = _sds(one_chip, (512,), jnp.uint8)
        _, text = _compile(apply_matrix_pallas_ragged, pool,
                           _rs_matrix(), mask, False, None)
        assert "tpu_custom_call" in text
    finally:
        jax.config.update("jax_enable_x64", prev)


@pytest.mark.parametrize("pool", [
    # the replicated pool's program takes ~2 minutes to compile here
    # (PERF.md §6, PR 21), so it runs with --runslow only
    pytest.param("replicated", marks=pytest.mark.slow),
    "ec"])
def test_bulk_crush_program_compiles(one_chip, pool):
    """The fused first-rung rule program at the chip smoke's cluster
    (1000 OSDs; 32,768 replicated / 8,192 EC 8+3 PGs), with its device
    memory checked against the v5e's 16 GB."""
    with jax.enable_x64(True):     # as bulk_do_rule traces it
        _bulk_crush_program_compiles(one_chip, pool)


def _bulk_crush_program_compiles(one_chip, pool):
    from ceph_tpu.cluster.topology import (EC_POOL, REPLICATED_POOL,
                                           ClusterSpec, build_cluster)
    from ceph_tpu.crush import bulk
    om = build_cluster(ClusterSpec.sized(
        1000, replicated_pg_num=32768, ec_k=8, ec_m=3, ec_pg_num=8192))
    p = om.pools[REPLICATED_POOL if pool == "replicated" else EC_POOL]
    cm = bulk.CompiledCrushMap(om.crush, None)
    tries = bulk.auto_tries(cm.cmap, p.crush_rule, p.size)
    rungs = bulk.auto_ladder(cm.cmap, p.crush_rule, p.size, tries)
    block = min(p.pg_num, bulk.auto_block(cm.cmap, p.crush_rule, p.size,
                                          rungs[0][0]))
    jf = bulk._get_jitted(cm, p.crush_rule, p.size, *rungs[0])
    xs = _sds(one_chip, (block,), jnp.int64)
    wv = _sds(one_chip, (len(om.osd_weight),), jnp.int64)
    compiled, _ = _compile(jf, xs, wv)
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < 16 << 30, f"bulk CRUSH program needs {used} bytes"
    # the block holds every PG of the pool or a fixed number of lanes
    assert block == p.pg_num or block == bulk.auto_block(
        cm.cmap, p.crush_rule, p.size, rungs[0][0])
    assert np.dtype(jnp.int64) == np.int64
