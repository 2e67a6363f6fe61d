"""The jit-facing entry-point registry — what tpu-audit certifies.

Every public surface that hands work to XLA is declared here with a
representative workload: the five plugin families' device-resident
encode/decode (byte and packed layouts), the engine dispatchers
(``apply_matrix_best`` / ``apply_matrix_packed_best``), the raw Pallas
kernels (interpret mode, so the kernel jaxpr itself is walked), the
fused decode→re-encode repair call, CRUSH bulk rule evaluation, and
scrub's batched CRC (a *host*-tier entry: its contract is that it never
dispatches through jax at all).

Each :class:`EntryPoint` declares:

- ``build()`` → a :class:`Built` carrying the callable, concrete
  representative args (small shapes — the audit is about code *shape*,
  not throughput), and the anchor function whose source file/line the
  findings attach to (``# tpu-lint: disable=audit-* -- reason`` pragmas
  near the anchor suppress, same syntax as the AST tier);
- ``allow`` — the expected jax primitive set for the family.  The
  auditor fails loudly on drift: a new primitive in a traced hot path
  is either a deliberate change (add it here, in review) or a
  regression (a float promotion, a host callback) that neither the AST
  linter nor the runtime verifier can see;
- ``float_ok`` — primitives allowed to produce inexact dtypes inside a
  GF-lane program (the whitelisted MXU bit-plane region; empty for
  everything else);
- ``trace_budget`` — compile-count ceiling for one cold run of the
  workload (the recompile sentinel's declared budget; a warm repeat
  must always be zero).

The registry is *declarative*: importing this module never imports jax
or the plugins — builders do, lazily — so the AST tier keeps working in
jax-free environments.

``registry_gaps()`` is the completeness gate: every public
``*_chunks*_jax`` surface reachable on a representative instance of
each family must be registered, so a new device surface cannot ship
unaudited.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Dict, FrozenSet, List, Optional, Tuple

# the five plugin families + the engine/ops/crush/scrub surfaces the
# acceptance gate requires coverage for, plus the telemetry plane
# (host-tier: its whole contract is "compiles nothing, ever"), the
# serving front-end (jit tier: the bucketed dispatch program; host
# tier: queue/batcher bookkeeping) and the cluster plane (jit tier:
# the balancer-round / storm-re-eval bulk programs over a
# topology-generated map + the rateless over-planned dispatch)
FAMILIES = ("jerasure", "isa", "shec", "lrc", "clay",
            "engine", "ops", "crush", "scrub", "telemetry", "serve",
            "cluster", "scenario", "tune", "chaos")

# public device surfaces a plugin family can expose; the completeness
# check requires every one present on a family's representative
# instance to be registered
PLUGIN_SURFACES = ("encode_chunks_jax", "decode_chunks_jax",
                   "encode_chunks_packed_jax", "decode_chunks_packed_jax")

B = 2          # representative batch
C = 4096       # representative chunk bytes (packed R = C/512 = 8 rows)
R = C // 512

REPRESENTATIVE_PROFILES: Dict[str, Tuple[str, Dict[str, str]]] = {
    # family -> (plugin name, profile) — mirrors the tier-1 test
    # matrices; small geometries, every code path identical to prod
    "jerasure": ("jerasure", {"technique": "reed_sol_van",
                              "k": "4", "m": "2"}),
    "jerasure_cauchy": ("jerasure", {"technique": "cauchy_good",
                                     "k": "4", "m": "2",
                                     "packetsize": "512"}),
    "isa": ("isa", {"k": "4", "m": "2"}),
    "shec": ("shec", {"k": "4", "m": "3", "c": "2"}),
    "lrc": ("lrc", {"k": "4", "m": "2", "l": "3"}),
    "clay": ("clay", {"k": "4", "m": "2", "d": "5"}),
}


@dataclasses.dataclass
class Built:
    """One buildable workload: the traced callable, its concrete
    representative args, and the source anchor findings attach to.
    ``x64``: trace and run with 64-bit types on (the crush rule
    programs, which crush/bulk.py runs under ``with_x64``)."""
    fn: Callable
    args: tuple
    anchor: Callable
    x64: bool = False

    def scope(self):
        """The context to trace and run ``fn`` in."""
        import jax
        return (jax.enable_x64(True) if self.x64
                else contextlib.nullcontext())


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    name: str                       # "clay.decode_chunks_jax"
    family: str                     # one of FAMILIES
    kind: str                       # "jit" | "host"
    build: Callable[[], Built]
    # expected primitive names (recursive over sub-jaxprs); None =
    # allowlist rule skipped (never used by registered entries — kept
    # for synthetic test entries)
    allow: Optional[FrozenSet[str]] = None
    # primitives allowed to carry inexact dtypes (MXU bit-plane region)
    float_ok: FrozenSet[str] = frozenset()
    # compile ceiling for one cold workload run (sentinel budget)
    trace_budget: int = 8


# ----------------------------------------------------------------------
# shared instance cache (builders are called repeatedly: audit once,
# sentinel twice)

_EC_CACHE: Dict[str, object] = {}


def representative_instance(family: str):
    """The family's representative plugin instance (cached)."""
    ec = _EC_CACHE.get(family)
    if ec is None:
        from ..codes.registry import ErasureCodePluginRegistry
        plugin, profile = REPRESENTATIVE_PROFILES[family]
        ec = ErasureCodePluginRegistry.instance().factory(
            plugin, dict(profile))
        _EC_CACHE[family] = ec
    return ec


def _erasure_pattern(ec):
    """Erase shard 1 — every family can repair one loss."""
    n = ec.get_chunk_count()
    erased = (1,)
    available = tuple(i for i in range(n) if i != 1)
    return available, erased


# ----------------------------------------------------------------------
# expected primitive sets (discovered by tracing on the pinned jax,
# reviewed, and baked — drift fails audit-primitive-allowlist).
#
# The SWAR XLA matrix path: u8<->u32 bitcasts + the shift/xor/and/mul
# xtime ladder under a jit wrapper, plus the static slice/concat
# plumbing the plugin surfaces add around it (transpose: the bitmatrix
# packet relayout).  Deliberately absent: gather / select_n /
# device_put — static index selection must lower to slices
# (ops/xla_ops.py::take_static), and any dynamic indirection in a GF
# program is drift worth reviewing.

GF_XLA_PRIMS = frozenset({
    "jit", "bitcast_convert_type", "reshape", "broadcast_in_dim",
    "concatenate", "slice", "squeeze", "transpose",
    "xor", "and", "or", "mul", "shift_left", "shift_right_logical",
})

# packed resident layout: same math, same set (the byte-view casts are
# bitcasts already in GF_XLA_PRIMS)
GF_PACKED_PRIMS = GF_XLA_PRIMS

# Pallas kernels traced in interpret mode additionally carry the
# interpreter's ref load/store primitives and the register pack's
# convert_element_type
GF_PALLAS_PRIMS = GF_XLA_PRIMS | frozenset({
    "pallas_call", "get", "swap", "convert_element_type", "pad",
})

# The MXU bit-sliced matmul: bit-plane expansion + one einsum.  Its
# float use is declared (float_ok), NOT absent — audit-float-lane
# checks every primitive around the sanctioned region (transpose: the
# einsum lowering moves the bf16 operand before the dot).
MXU_FLOAT_OK = frozenset({"convert_element_type", "dot_general",
                          "transpose"})
GF_MXU_PRIMS = GF_XLA_PRIMS | frozenset({
    "dot_general", "add", "iota", "select_n", "eq", "ne", "lt",
    "transpose", "reduce_sum", "dynamic_slice", "pad", "gather",
    "convert_element_type",
})

# The XOR-scheduled kernel family (ISSUE 12, ops/xor_schedule.py +
# ops/pallas_gf.py): scheduled programs are straight-line XOR/shift
# chains over SWAR words — mul-free by construction (the xtime step
# decomposes its feedback into shift taps) and gather-free like every
# GF program.  A ``mul`` or table-gather appearing in a scheduled
# program is a FINDING: it means the schedule leaked back into the
# dense multiply path the scheduler exists to replace.
GF_XOR_PRIMS = frozenset({
    "jit", "bitcast_convert_type", "reshape", "broadcast_in_dim",
    "concatenate", "slice", "squeeze", "transpose",
    "xor", "and", "or", "shift_left", "shift_right_logical",
})

GF_XOR_PALLAS_PRIMS = GF_XOR_PRIMS | frozenset({
    "pallas_call", "get", "swap", "convert_element_type", "pad",
})

# The mesh-sharded engine tier (ISSUE 8, parallel/plane.py): the same
# GF program per shard under ONE shard_map, plus the zero-stripe pad
# for non-dividing batches.  Anything else appearing in a sharded
# program (a collective, a gather) is drift worth reviewing — the
# stripe-sharded tier must stay communication-free.
GF_SHARD_PRIMS = GF_XLA_PRIMS | frozenset({"shard_map", "pad"})

# CRUSH bulk rule evaluation: straw2 fixed-point draws, rjenkins hash
# mixing, candidate-grid scans/fixpoints — integer end to end (gather
# IS expected here: bucket item lookup is genuinely dynamic in x)
CRUSH_BULK_PRIMS = frozenset({
    "jit", "broadcast_in_dim", "reshape", "concatenate", "squeeze",
    "slice", "gather", "scatter", "transpose", "convert_element_type",
    "iota", "add", "sub", "mul", "neg", "sign", "and", "or", "xor",
    "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "eq", "ne", "lt", "le", "gt", "ge",
    "select_n", "max", "min", "rem", "div",
    "reduce_and", "reduce_or", "reduce_max", "reduce_min",
    "reduce_sum", "argmax", "argmin", "scan", "while", "cond",
    "clamp", "dynamic_slice", "dynamic_update_slice", "pad",
})


# ----------------------------------------------------------------------
# builders

def _plugin_surface_builder(family: str, surface: str) -> Callable[[], Built]:
    def build() -> Built:
        import numpy as np

        ec = representative_instance(family)
        k = ec.get_data_chunk_count()
        available, erased = _erasure_pattern(ec)
        anchor = getattr(type(ec), surface)
        if surface == "encode_chunks_jax":
            args = (np.zeros((B, k, C), np.uint8),)
            fn = ec.encode_chunks_jax
        elif surface == "decode_chunks_jax":
            args = (np.zeros((B, len(available), C), np.uint8),)
            fn = (lambda chunks, _ec=ec, _a=available, _e=erased:
                  _ec.decode_chunks_jax(chunks, _a, _e))
        elif surface == "encode_chunks_packed_jax":
            args = (np.zeros((B, k, R, 128), np.uint32),)
            fn = ec.encode_chunks_packed_jax
        else:  # decode_chunks_packed_jax
            args = (np.zeros((B, len(available), R, 128), np.uint32),)
            fn = (lambda words, _ec=ec, _a=available, _e=erased:
                  _ec.decode_chunks_packed_jax(words, _a, _e))
        return Built(fn, args, anchor)

    return build


def _rs_static():
    """The jerasure RS (m, k) coding matrix as the hashable static
    tuple — the representative small matrix for the ops entries."""
    from ..ops.xla_ops import matrix_to_static

    ec = representative_instance("jerasure")
    return matrix_to_static(ec.matrix)


def _build_apply_matrix_best() -> Built:
    import numpy as np

    from ..ops.pallas_gf import apply_matrix_best

    ms = _rs_static()
    return Built(lambda x: apply_matrix_best(x, ms, 8),
                 (np.zeros((B, 4, C), np.uint8),), apply_matrix_best)


def _build_apply_matrix_packed_best() -> Built:
    import numpy as np

    from ..ops.pallas_gf import apply_matrix_packed_best

    ms = _rs_static()
    return Built(lambda x: apply_matrix_packed_best(x, ms),
                 (np.zeros((B, 4, R, 128), np.uint32),),
                 apply_matrix_packed_best)


def _build_pallas_byte() -> Built:
    import numpy as np

    from ..ops.pallas_gf import apply_matrix_pallas

    ms = _rs_static()
    return Built(lambda x: apply_matrix_pallas(x, ms, True),
                 (np.zeros((B, 4, C), np.uint8),), apply_matrix_pallas)


def _build_pallas_packed() -> Built:
    import numpy as np

    from ..ops.pallas_gf import apply_matrix_pallas_packed

    ms = _rs_static()
    return Built(lambda x: apply_matrix_pallas_packed(x, ms, True),
                 (np.zeros((B, 4, R, 128), np.uint32),),
                 apply_matrix_pallas_packed)


def _build_apply_matrix_mxu() -> Built:
    """The MXU bit-sliced GF(2) matmul, traced directly (the selection
    table only routes composites here on TPU, so the deterministic
    XLA-tier audit must reach it explicitly).  Its bf16/f32 use is the
    ONE sanctioned float region — exact by construction (0/1 planes,
    integral f32 sums; ops/xla_ops.py) — declared via float_ok rather
    than pragma-suppressed, so audit-float-lane still guards every
    primitive around it."""
    import numpy as np

    from ..ops.xla_ops import apply_matrix_mxu

    ms = _rs_static()
    return Built(lambda x: apply_matrix_mxu(x, ms),
                 (np.zeros((B, 4, C), np.uint8),), apply_matrix_mxu)


def _build_pallas_bitmatrix() -> Built:
    import numpy as np

    from ..ops.pallas_gf import apply_bitmatrix_pallas
    from ..ops.xla_ops import bitmatrix_to_static

    ec = representative_instance("jerasure_cauchy")
    rows = bitmatrix_to_static(ec.bitmatrix)
    w, packetsize = ec.w, 512
    return Built(lambda x: apply_bitmatrix_pallas(x, rows, w, packetsize,
                                                  True),
                 (np.zeros((B, 4, w * packetsize), np.uint8),),
                 apply_bitmatrix_pallas)


# representative XOR schedules (ops/xor_schedule.py): one CSE
# schedule that exercises the xtime plane chain (an entry of 2 forces
# one doubling), one ring-transform schedule (monomial matrix: shift
# pairs + the feedback fold) that the probe actually PREFERS — both
# deterministic pure functions of the pinned matrices

def _xor_cse_static():
    from ..ops.xor_schedule import build_schedule

    ms = ((1, 1, 1, 1, 0, 0, 0), (0, 0, 1, 1, 1, 1, 0),
          (2, 0, 0, 0, 1, 1, 1))
    return build_schedule(ms).static


def _xor_ring_static():
    from ..ops.xor_schedule import build_schedule

    ms = ((1, 1, 1, 1, 1, 1, 1), (1, 2, 4, 8, 16, 32, 64))
    sched = build_schedule(ms)
    assert sched.transform == "ring", sched.transform
    return sched.static


def _build_xor_pallas() -> Built:
    import numpy as np

    from ..ops.pallas_gf import apply_matrix_xor_pallas

    sched = _xor_cse_static()
    return Built(lambda x: apply_matrix_xor_pallas(x, sched, True),
                 (np.zeros((B, 7, C), np.uint8),),
                 apply_matrix_xor_pallas)


def _build_xor_packed() -> Built:
    import numpy as np

    from ..ops.pallas_gf import apply_matrix_xor_packed

    sched = _xor_ring_static()
    return Built(lambda x: apply_matrix_xor_packed(x, sched, True),
                 (np.zeros((B, 7, R, 128), np.uint32),),
                 apply_matrix_xor_packed)


def _build_xor_xla() -> Built:
    import numpy as np

    from ..ops.pallas_gf import apply_matrix_xor_xla

    sched = _xor_cse_static()
    return Built(lambda x: apply_matrix_xor_xla(x, sched),
                 (np.zeros((B, 7, C), np.uint8),),
                 apply_matrix_xor_xla)


def _build_bitmatrix_xor() -> Built:
    """The CSE-scheduled packet-layout kernel, on a bitmatrix whose
    greedy sharing actually pays (cauchy_orig k=4,m=2 — the probe
    schedules it; the audit fails loudly if that stops being true,
    because the entry would then trace the WRONG kernel)."""
    import numpy as np

    from ..codes.registry import ErasureCodePluginRegistry
    from ..ops.pallas_gf import apply_bitmatrix_xor_pallas
    from ..ops.xla_ops import bitmatrix_to_static
    from ..ops.xor_schedule import probe_bitmatrix_schedule

    ec = ErasureCodePluginRegistry.instance().factory(
        "jerasure", {"technique": "cauchy_orig", "k": "4", "m": "2",
                     "packetsize": "512"})
    rows = bitmatrix_to_static(ec.bitmatrix)
    sched = probe_bitmatrix_schedule(rows, ec.w)
    assert sched is not None, "cauchy_orig bitmatrix must schedule"
    w, packetsize = ec.w, 512
    return Built(
        lambda x: apply_bitmatrix_xor_pallas(x, sched.static, w,
                                             packetsize, True),
        (np.zeros((B, 4, w * packetsize), np.uint8),),
        apply_bitmatrix_xor_pallas)


def _build_fused_repair() -> Built:
    import numpy as np

    from ..codes.engine import fused_repair_call

    ec = representative_instance("jerasure")
    available, erased = _erasure_pattern(ec)
    fn = fused_repair_call(ec, available, erased)
    return Built(fn, (np.zeros((B, len(available), C), np.uint8),),
                 fused_repair_call)


# ----------------------------------------------------------------------
# mesh-sharded variants (ISSUE 8): the SAME programs with the stripe
# batch sharded over an explicit plane spanning every visible device.
# On a single-device run (the bare `tpu_lint --trace` process) the
# plane degrades to the single-device program — the allowlists are
# supersets, so the audit stays green either way; the simulated-mesh
# gate in tools/test_full.sh re-audits these entries under
# XLA_FLAGS=--xla_force_host_platform_device_count=8, where the
# shard_map shape is real.

_SHARD_B = 8  # divides every power-of-two mesh (1/2/4/8 devices)


def _mesh_plane_all():
    """An explicit DataPlane over every visible device (tp=1)."""
    import jax

    from ..parallel.mesh import make_mesh
    from ..parallel.plane import DataPlane

    return DataPlane(make_mesh(len(jax.devices()), tp=1))


def _build_fused_repair_sharded() -> Built:
    import numpy as np

    from ..codes.engine import fused_repair_call

    ec = representative_instance("jerasure")
    available, erased = _erasure_pattern(ec)
    fn = fused_repair_call(ec, available, erased, mesh=_mesh_plane_all())
    return Built(fn, (np.zeros((_SHARD_B, len(available), C), np.uint8),),
                 fused_repair_call)


def _build_serve_dispatch_sharded() -> Built:
    import numpy as np

    from ..codes.engine import serve_dispatch_call

    ec = representative_instance("jerasure")
    k = ec.get_data_chunk_count()
    fn = serve_dispatch_call(ec, "encode", mesh=_mesh_plane_all())
    return Built(fn, (np.zeros((_SHARD_B, k, C), np.uint8),),
                 serve_dispatch_call)


def _mesh_plane_hosts():
    """The all-device plane split into host fault domains (ISSUE 17):
    same mesh, host-major partition.  Falls back to one domain when
    the device count cannot halve (the bare single-device audit)."""
    import jax

    from ..parallel.mesh import make_mesh
    from ..parallel.plane import DataPlane

    n = len(jax.devices())
    h = 2 if n >= 2 and n % 2 == 0 else 1
    return DataPlane(make_mesh(n, tp=1), hosts=h)


def _build_fused_repair_host_sharded() -> Built:
    """The fused repair program sharded over a HOST-PARTITIONED plane
    (ISSUE 17), on its own erasure pattern so it audits its own cache
    row: the host split is dispatch-plane bookkeeping only, so the
    program must stay primitive-identical to the single-domain
    sharded build (GF_SHARD_PRIMS) and the warm == 0 sentinel pins
    that spanning fault domains never recompiles."""
    import numpy as np

    from ..codes.engine import fused_repair_call

    ec = representative_instance("jerasure")
    n = ec.get_chunk_count()
    erased = (2,)
    available = tuple(i for i in range(n) if i != 2)
    fn = fused_repair_call(ec, available, erased,
                           mesh=_mesh_plane_hosts())
    return Built(fn, (np.zeros((_SHARD_B, len(available), C),
                               np.uint8),),
                 fused_repair_call)


def _build_apply_matrix_best_sharded() -> Built:
    import numpy as np

    from ..ops.pallas_gf import apply_matrix_best

    ms = _rs_static()
    plane = _mesh_plane_all()
    return Built(lambda x: apply_matrix_best(x, ms, 8, mesh=plane),
                 (np.zeros((_SHARD_B, 4, C), np.uint8),),
                 apply_matrix_best)


def _build_crush_bulk_sharded() -> Built:
    """The fused rule program jitted with the x batch sharded over the
    plane (NamedSharding in/out — the crush/bulk.py mesh path).  Same
    primitives as the single-device program: GSPMD sharding adds no
    eqns, which is exactly the property worth pinning."""
    import numpy as np

    hit = _CRUSH_CACHE.get("bulk_sharded")
    if hit is None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..crush import (CrushBuilder, step_chooseleaf_indep,
                             step_emit, step_take)
        from ..crush.bulk import CompiledCrushMap, compile_rule

        plane = _mesh_plane_all()
        b = CrushBuilder()
        root = b.build_two_level(4, 2)
        b.add_rule(0, [step_take(root), step_chooseleaf_indep(0, 1),
                       step_emit()])
        cm = CompiledCrushMap(b.map)
        fn = compile_rule(cm, 0, 3)
        shard = NamedSharding(plane.mesh, P(plane.axis))
        repl = NamedSharding(plane.mesh, P())
        jf = jax.jit(jax.vmap(fn, in_axes=(0, None)),
                     in_shardings=(shard, repl),
                     out_shardings=(shard, shard, shard))
        with jax.enable_x64(True):
            wv = jnp.asarray(np.asarray(b.map.device_weights(),
                                        dtype=np.int64))
            xs = jnp.asarray(np.arange(_SHARD_B, dtype=np.int64))
        hit = (jf, xs, wv, compile_rule)
        _CRUSH_CACHE["bulk_sharded"] = hit
    jf, xs, wv, anchor = hit
    return Built(jf, (xs, wv), anchor, x64=True)


_CRUSH_CACHE: dict = {}


def _build_crush_bulk() -> Built:
    import numpy as np

    hit = _CRUSH_CACHE.get("bulk")
    if hit is None:
        import jax
        import jax.numpy as jnp

        from ..crush import (CrushBuilder, step_chooseleaf_indep,
                             step_emit, step_take)
        from ..crush.bulk import CompiledCrushMap, compile_rule

        b = CrushBuilder()
        root = b.build_two_level(4, 2)
        b.add_rule(0, [step_take(root), step_chooseleaf_indep(0, 1),
                       step_emit()])
        cm = CompiledCrushMap(b.map)
        fn = compile_rule(cm, 0, 3)
        jf = jax.jit(jax.vmap(fn, in_axes=(0, None)))
        with jax.enable_x64(True):
            wv = jnp.asarray(np.asarray(b.map.device_weights(),
                                        dtype=np.int64))
            xs = jnp.asarray(np.arange(8, dtype=np.int64))
        hit = (jf, xs, wv, compile_rule)
        _CRUSH_CACHE["bulk"] = hit
    jf, xs, wv, anchor = hit
    return Built(jf, (xs, wv), anchor, x64=True)


# ----------------------------------------------------------------------
# cluster plane (ISSUE 9): the jitted programs the 10k-OSD workloads
# drive.  The balancer round and the storm re-eval are BOTH the fused
# crush rule program, but over a topology-generated production-shape
# map (4-level root→rack→host→osd tree): the replicated
# chooseleaf-firstn rule is the balancer loop's per-round evaluation,
# the canonical EC chooseleaf-indep rule (SET steps, scan/while
# fixpoints) is what every storm epoch re-evaluates.  Small spec —
# the audit is about program shape, not throughput.

def _cluster_map():
    hit = _CRUSH_CACHE.get("cluster_map")
    if hit is None:
        from ..cluster.topology import ClusterSpec, build_cluster
        spec = ClusterSpec(seed=5, racks=3, hosts_per_rack=2,
                           osds_per_host=2, replicated_pg_num=16,
                           ec_pg_num=8, ec_k=2, ec_m=1)
        hit = build_cluster(spec)
        _CRUSH_CACHE["cluster_map"] = hit
    return hit


def _cluster_rule_built(pool_id: int, cache_key: str) -> Built:
    import numpy as np

    hit = _CRUSH_CACHE.get(cache_key)
    if hit is None:
        import jax
        import jax.numpy as jnp

        from ..crush.bulk import CompiledCrushMap, compile_rule

        m = _cluster_map()
        pool = m.pools[pool_id]
        cm = CompiledCrushMap(m.crush)
        fn = compile_rule(cm, pool.crush_rule, pool.size)
        jf = jax.jit(jax.vmap(fn, in_axes=(0, None)))
        with jax.enable_x64(True):
            wv = jnp.asarray(np.asarray(m.osd_weight, dtype=np.int64))
            xs = jnp.asarray(np.asarray(pool.pps_all()[:8],
                                        dtype=np.int64))
        hit = (jf, xs, wv, compile_rule)
        _CRUSH_CACHE[cache_key] = hit
    jf, xs, wv, anchor = hit
    return Built(jf, (xs, wv), anchor, x64=True)


def _build_cluster_balancer_round() -> Built:
    from ..cluster.topology import REPLICATED_POOL

    return _cluster_rule_built(REPLICATED_POOL, "cluster_balancer")


def _build_cluster_storm_reeval() -> Built:
    from ..cluster.topology import EC_POOL

    return _cluster_rule_built(EC_POOL, "cluster_storm")


def _build_cluster_rateless_dispatch() -> Built:
    """The device program one over-planned rateless copy dispatches
    (cluster/rateless.py::rateless_dispatch_call = the engine's fused
    decode→re-encode repair program).  Distinct erasure pattern from
    the engine.fused_repair_call entry, so this audits its own cached
    program."""
    import numpy as np

    from ..cluster.rateless import rateless_dispatch_call

    ec = representative_instance("jerasure")
    n = ec.get_chunk_count()
    erased = (2,)
    available = tuple(i for i in range(n) if i != 2)
    fn = rateless_dispatch_call(ec, available, erased)
    return Built(fn, (np.zeros((B, len(available), C), np.uint8),),
                 rateless_dispatch_call)


def _build_crc_batch() -> Built:
    import numpy as np

    from ..codes.stripe import ceph_crc32c_batch

    crcs = np.full(B, 0xFFFFFFFF, np.uint32)
    bufs = np.zeros((B, 2 * C), np.uint8)
    return Built(ceph_crc32c_batch, (crcs, bufs), ceph_crc32c_batch)


def _build_serve_dispatch() -> Built:
    """The serving batcher's bucketed device dispatch
    (engine.serve_dispatch_call): the jitted per-(plugin, profile, op,
    pattern) program a shape bucket fires.  Traced on the
    representative RS encode bucket at a mid-ladder rung — the audit
    certifies the program shape; the zero-warm-recompile property over
    a full request stream is pinned by tests/test_serve.py on top of
    this entry's warm == 0 sentinel."""
    import numpy as np

    from ..codes.engine import serve_dispatch_call

    ec = representative_instance("jerasure")
    k = ec.get_data_chunk_count()
    fn = serve_dispatch_call(ec, "encode")
    return Built(fn, (np.zeros((4, k, C), np.uint8),),
                 serve_dispatch_call)


def _build_serve_batcher() -> Built:
    """Queue/batcher/SLO bookkeeping as a host-tier entry: a seeded
    closed-loop mini-scenario on a FakeClock with the host executor
    runs admission → bucketing → deadline-slack firing → SLO report
    end to end and must trigger ZERO jax compiles and return zero
    device arrays — the serving front door stays host bookkeeping by
    construction."""
    from ..serve.batcher import ContinuousBatcher
    from ..serve.loadgen import (CodecSpec, TrafficSpec,
                                 run_serving_scenario,
                                 throughput_service_model)
    from ..utils.retry import FakeClock

    spec = TrafficSpec(
        seed=11, n_requests=12,
        codecs=[CodecSpec("rs_k2_m1", "jerasure",
                          {"technique": "reed_sol_van",
                           "k": "2", "m": "1"}, 512)],
        ladder=(1, 2, 4), concurrency=6)

    def workload():
        run = run_serving_scenario(
            spec, clock=FakeClock(), executor="host",
            service_model=throughput_service_model())
        return run.report

    return Built(workload, (), ContinuousBatcher.poll)


def _build_telemetry() -> Built:
    """The telemetry plane as a host-tier entry: spans + histograms +
    registry + both exporters run end to end (telemetry_selftest) and
    must trigger ZERO jax compiles and return zero device arrays —
    the recompile sentinel is the enforcement that instrumentation
    can never leak into (or pull work onto) the device."""
    from ..telemetry import telemetry_selftest

    return Built(telemetry_selftest, (), telemetry_selftest)


def _build_profiler_selftest() -> Built:
    """The device-plane profiler's attribution join as a host-tier
    entry (ISSUE 10): capture → observe → roofline rows → schema
    validation on synthetic analytic costs.  The profiler's whole
    value is that cost capture never backend-compiles; this sentinel
    pins the join side of it to ZERO compiles forever (the lower-only
    capture path is exercised — and compile-counted — by the jit
    entries it rides)."""
    from ..telemetry.profiler import profiler_selftest

    return Built(profiler_selftest, (), profiler_selftest)


def _build_flight_recorder() -> Built:
    """The flight recorder as a host-tier entry (ISSUE 10): ring
    bounding, span-root wiring, post-mortem dump + delta accounting
    and schema validation on isolated clock-injected instances —
    ZERO compiles, zero device arrays.  A post-mortem path that
    touched the device would deadlock exactly when it matters (the
    device is what just failed)."""
    from ..telemetry.recorder import flight_recorder_selftest

    return Built(flight_recorder_selftest, (), flight_recorder_selftest)


def _build_scenario_runner() -> Built:
    """The composed production-day scenario as a host-tier entry
    (ISSUE 11): cluster build, store staging, client stream, churn,
    recovery rounds and scrub ticks under the mClock arbiter, end to
    end on a FakeClock — ZERO jax compiles, zero device arrays,
    forever.  The composition layer is host scheduling by
    construction; its only device seams are the already-audited
    serve.dispatch / engine.fused_repair_call programs."""
    from ..scenario.runner import scenario_selftest

    return Built(scenario_selftest, (), scenario_selftest)


def _build_week_runner() -> Built:
    """The multi-tenant compressed week as a host-tier entry
    (ISSUE 19): per-tenant diurnal streams under the per-tenant
    mClock door, discrete-event fast-forward, and all four staged
    disasters (rack/backend/host loss + burst storm) healing
    byte-identically — end to end on an EventClock, ZERO jax
    compiles, zero device arrays, forever.  Week orchestration is
    host bookkeeping by construction; its only device seams are the
    already-audited serve/engine programs."""
    from ..scenario.week import week_selftest

    return Built(week_selftest, (), week_selftest)


def _build_supervisor_selftest() -> Built:
    """The supervised dispatch plane as a host-tier entry (ISSUE 13):
    the full classification ladder — transient retry, OOM rung split,
    persistent-loss demotion to the ground-truth twin, corrupt-output
    self-verify, health-probe re-promotion — on isolated FakeClock
    state: ZERO jax compiles, zero device arrays, forever.  A
    recovery plane that itself needed the device would deadlock
    exactly when the device is what just failed."""
    from ..ops.supervisor import supervisor_selftest

    return Built(supervisor_selftest, (), supervisor_selftest)


def _build_host_chaos_selftest() -> Built:
    """The host fault-domain survival arc as a host-tier entry
    (ISSUE 17): a seeded HostLoss against an isolated supervisor —
    host-granular reshrink, journal-reclaim hook, health-probe
    re-promotion restoring the original topology (or, on a
    single-device floor, the planeless demote-to-twin ladder) — on
    pure-numpy callables: ZERO jax compiles, zero device arrays,
    forever.  The plane bookkeeping (mesh build, activate/restore) is
    the only jax surface and it compiles nothing."""
    from ..chaos.hosts import host_chaos_selftest

    return Built(host_chaos_selftest, (), host_chaos_selftest)


def _build_fused_repair_supervised() -> Built:
    """The supervised fused-repair seam as a jit-tier entry: the SAME
    cached decode→re-encode program under the supervisor's eager
    wrapper, on its own erasure pattern so it audits its own cached
    program.  Tracing must see the raw program only (the wrapper
    gates on tracer-ness), so supervision adds ZERO primitives and
    the warm==0 sentinel pins that a supervised clean path never
    recompiles."""
    import numpy as np

    from ..codes.engine import fused_repair_call

    ec = representative_instance("jerasure")
    n = ec.get_chunk_count()
    erased = (3,)
    available = tuple(i for i in range(n) if i != 3)
    fn = fused_repair_call(ec, available, erased)
    return Built(fn, (np.zeros((B, len(available), C), np.uint8),),
                 fused_repair_call)


def _build_tune_sweep() -> Built:
    """The roofline-closing autotuner's analytic sweep as a host-tier
    entry (ISSUE 14): a seeded sweep over the representative corpus,
    run twice and pinned byte-identical, the emitted best-config
    table schema-validated and round-tripped — ZERO jax compiles and
    zero device arrays, forever."""
    from ..tune.sweep import tune_sweep_selftest

    return Built(tune_sweep_selftest, (), tune_sweep_selftest)


def _build_tracing_selftest() -> Built:
    """The causal tracing plane as a host-tier entry (ISSUE 15): a
    seeded FakeClock mini-scenario through the REAL serving seams
    with a collector installed, decomposed by the analyzer (segment
    sums exact), both exports rendered and schema-validated — ZERO
    jax compiles, zero device arrays, forever.  A tracing plane that
    pulled work onto the device would distort exactly the tails it
    exists to attribute."""
    from ..telemetry.tracing import tracing_selftest

    return Built(tracing_selftest, (), tracing_selftest)


def _build_scenario_qos() -> Built:
    """The mClock arbiter as a host-tier entry (ISSUE 11):
    reservation floor, weight pacing, limit ceiling and burn-rate
    scaling exercised on a FakeClock — ZERO compiles, zero device
    arrays.  QoS arbitration that touched the device would contend
    with exactly the work it schedules."""
    from ..scenario.qos import qos_selftest

    return Built(qos_selftest, (), qos_selftest)


# ----------------------------------------------------------------------
# THE registry

def _plugin_entries() -> List[EntryPoint]:
    entries: List[EntryPoint] = []
    surfaces = {
        "jerasure": PLUGIN_SURFACES,
        "jerasure_cauchy": ("encode_chunks_jax", "decode_chunks_jax"),
        "isa": PLUGIN_SURFACES,
        "shec": ("encode_chunks_jax", "decode_chunks_jax",
                 "encode_chunks_packed_jax", "decode_chunks_packed_jax"),
        "lrc": PLUGIN_SURFACES,
        "clay": PLUGIN_SURFACES,
    }
    for family, surfs in surfaces.items():
        base = family.split("_")[0] if family != "jerasure_cauchy" \
            else "jerasure"
        for surface in surfs:
            entries.append(EntryPoint(
                name=f"{family}.{surface}",
                family=base,
                kind="jit",
                build=_plugin_surface_builder(family, surface),
                allow=GF_PACKED_PRIMS if "packed" in surface
                else GF_XLA_PRIMS,
                trace_budget=24,
            ))
    return entries


def registry() -> Tuple[EntryPoint, ...]:
    """Every audited entry point, in deterministic audit order."""
    entries = _plugin_entries()
    entries += [
        EntryPoint("ops.apply_matrix_best", "ops", "jit",
                   _build_apply_matrix_best, allow=GF_XLA_PRIMS,
                   trace_budget=16),
        EntryPoint("ops.apply_matrix_packed_best", "ops", "jit",
                   _build_apply_matrix_packed_best,
                   allow=GF_PACKED_PRIMS, trace_budget=16),
        EntryPoint("ops.apply_matrix_pallas", "ops", "jit",
                   _build_pallas_byte, allow=GF_PALLAS_PRIMS,
                   trace_budget=16),
        EntryPoint("ops.apply_matrix_pallas_packed", "ops", "jit",
                   _build_pallas_packed, allow=GF_PALLAS_PRIMS,
                   trace_budget=16),
        EntryPoint("ops.apply_bitmatrix_pallas", "ops", "jit",
                   _build_pallas_bitmatrix, allow=GF_PALLAS_PRIMS,
                   trace_budget=16),
        EntryPoint("ops.apply_matrix_mxu", "ops", "jit",
                   _build_apply_matrix_mxu, allow=GF_MXU_PRIMS,
                   float_ok=MXU_FLOAT_OK, trace_budget=16),
        # the XOR-scheduled kernel family (ISSUE 12): interpret-mode
        # Pallas (byte + packed) and the XLA build of the same
        # schedules, pinned to the XOR-only allowlist — a mul or
        # gather in a scheduled program is a finding forever
        EntryPoint("ops.apply_matrix_xor_pallas", "ops", "jit",
                   _build_xor_pallas, allow=GF_XOR_PALLAS_PRIMS,
                   trace_budget=16),
        EntryPoint("ops.apply_matrix_xor_packed", "ops", "jit",
                   _build_xor_packed, allow=GF_XOR_PALLAS_PRIMS,
                   trace_budget=16),
        EntryPoint("ops.apply_matrix_xor_xla", "ops", "jit",
                   _build_xor_xla, allow=GF_XOR_PRIMS,
                   trace_budget=16),
        EntryPoint("ops.apply_bitmatrix_xor", "ops", "jit",
                   _build_bitmatrix_xor, allow=GF_XOR_PALLAS_PRIMS,
                   trace_budget=16),
        EntryPoint("engine.fused_repair_call", "engine", "jit",
                   _build_fused_repair, allow=GF_XLA_PRIMS,
                   trace_budget=16),
        # the mesh-sharded tier (ISSUE 8): the same programs sharded
        # over an explicit all-device plane; the simulated-mesh gate
        # re-audits them at device_count=8
        EntryPoint("engine.fused_repair_sharded", "engine", "jit",
                   _build_fused_repair_sharded, allow=GF_SHARD_PRIMS,
                   trace_budget=16),
        EntryPoint("serve.dispatch_sharded", "serve", "jit",
                   _build_serve_dispatch_sharded, allow=GF_SHARD_PRIMS,
                   trace_budget=16),
        # host fault domains (ISSUE 17): the same sharded repair
        # program over a host-partitioned plane — the domain split is
        # bookkeeping, so primitives and warm-compile count must not
        # move; the survival arc itself is the host-tier entry below
        EntryPoint("engine.fused_repair_host_sharded", "engine", "jit",
                   _build_fused_repair_host_sharded,
                   allow=GF_SHARD_PRIMS, trace_budget=16),
        EntryPoint("ops.apply_matrix_best_sharded", "ops", "jit",
                   _build_apply_matrix_best_sharded,
                   allow=GF_SHARD_PRIMS, trace_budget=16),
        EntryPoint("crush.bulk_rule_sharded", "crush", "jit",
                   _build_crush_bulk_sharded, allow=CRUSH_BULK_PRIMS,
                   trace_budget=24),
        EntryPoint("crush.bulk_rule", "crush", "jit",
                   _build_crush_bulk, allow=CRUSH_BULK_PRIMS,
                   trace_budget=24),
        EntryPoint("scrub.ceph_crc32c_batch", "scrub", "host",
                   _build_crc_batch, allow=None, trace_budget=0),
        EntryPoint("telemetry.selftest", "telemetry", "host",
                   _build_telemetry, allow=None, trace_budget=0),
        EntryPoint("telemetry.profiler_selftest", "telemetry", "host",
                   _build_profiler_selftest, allow=None,
                   trace_budget=0),
        EntryPoint("telemetry.flight_recorder", "telemetry", "host",
                   _build_flight_recorder, allow=None, trace_budget=0),
        # the causal tracing plane (ISSUE 15): trace mint/propagation,
        # segment decomposition and both exports are host bookkeeping
        # forever — 0 compiles, 0 device arrays (its only device
        # adjacency is READING the profiler series name at the
        # already-audited engine seams)
        EntryPoint("telemetry.tracing", "telemetry", "host",
                   _build_tracing_selftest, allow=None,
                   trace_budget=0),
        EntryPoint("serve.dispatch", "serve", "jit",
                   _build_serve_dispatch, allow=GF_XLA_PRIMS,
                   trace_budget=16),
        EntryPoint("serve.batcher", "serve", "host",
                   _build_serve_batcher, allow=None, trace_budget=0),
        # the cluster plane (ISSUE 9): balancer-round + storm-re-eval
        # bulk programs over a topology-generated 4-level map, and the
        # rateless over-planned dispatch (the fused repair program a
        # first-k copy runs) — all warm == 0 like every jit entry
        EntryPoint("cluster.balancer_round", "cluster", "jit",
                   _build_cluster_balancer_round,
                   allow=CRUSH_BULK_PRIMS, trace_budget=24),
        EntryPoint("cluster.storm_reeval", "cluster", "jit",
                   _build_cluster_storm_reeval,
                   allow=CRUSH_BULK_PRIMS, trace_budget=24),
        EntryPoint("cluster.rateless_dispatch", "cluster", "jit",
                   _build_cluster_rateless_dispatch,
                   allow=GF_XLA_PRIMS, trace_budget=16),
        # the scenario composition layer (ISSUE 11): the runner and
        # the QoS arbiter are host scheduling forever — 0 compiles,
        # 0 device arrays (their device seams are the audited serve/
        # engine programs above)
        EntryPoint("scenario.runner", "scenario", "host",
                   _build_scenario_runner, allow=None, trace_budget=0),
        EntryPoint("scenario.qos", "scenario", "host",
                   _build_scenario_qos, allow=None, trace_budget=0),
        # the multi-tenant compressed week (ISSUE 19): discrete-event
        # orchestration + per-tenant mClock + staged disasters are
        # host scheduling forever — 0 compiles, 0 device arrays
        EntryPoint("scenario.week", "scenario", "host",
                   _build_week_runner, allow=None, trace_budget=0),
        # the supervised dispatch plane (ISSUE 13): the supervisor is
        # host control flow forever (0 compiles, 0 device arrays),
        # and the supervised fused-repair seam's program is the raw
        # cached program — the wrapper is invisible to tracing, so a
        # primitive appearing here that the unsupervised entry lacks
        # would mean supervision leaked into the jaxpr
        EntryPoint("ops.supervisor", "ops", "host",
                   _build_supervisor_selftest, allow=None,
                   trace_budget=0),
        # the host fault-domain survival arc (ISSUE 17): loss ->
        # host-granular reshrink -> journal reclaim -> re-promotion,
        # all host control flow forever — 0 compiles, 0 device arrays
        # (the recovery plane must not need the thing that just died)
        EntryPoint("chaos.host_plane", "chaos", "host",
                   _build_host_chaos_selftest, allow=None,
                   trace_budget=0),
        EntryPoint("engine.fused_repair_supervised", "engine", "jit",
                   _build_fused_repair_supervised, allow=GF_XLA_PRIMS,
                   trace_budget=16),
        # the roofline-closing autotuner (ISSUE 14): the analytic
        # sweep is host arithmetic forever — 0 compiles, 0 device
        # arrays (its timed twin measures the already-audited engine
        # programs; tuned CONFIGS are re-certified by the tuned-table
        # audit test in tests/test_autotune.py)
        EntryPoint("tune.sweep", "tune", "host",
                   _build_tune_sweep, allow=None, trace_budget=0),
    ]
    return tuple(entries)


def registry_names() -> List[str]:
    return [e.name for e in registry()]


def registry_gaps() -> List[str]:
    """Public plugin device surfaces missing from the registry — the
    completeness gate (a new ``*_chunks*_jax`` surface on any family's
    representative class MUST be declared here to ship)."""
    registered = {e.name for e in registry()}
    gaps: List[str] = []
    for family in REPRESENTATIVE_PROFILES:
        ec = representative_instance(family)
        for surface in PLUGIN_SURFACES:
            if callable(getattr(type(ec), surface, None)) \
                    and f"{family}.{surface}" not in registered:
                gaps.append(f"{family}.{surface}")
    return gaps
