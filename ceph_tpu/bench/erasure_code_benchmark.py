"""ErasureCodeBench — the metric source, CLI-compatible with the reference.

Mirrors src/test/erasure-code/ceph_erasure_code_benchmark.{h,cc} ->
class ErasureCodeBench:
- setup(): boost::program_options flags --plugin/-p, --workload/-w
  encode|decode, --iterations/-i, --size/-s, --parameter/-P (repeated
  k=v into the ErasureCodeProfile), --erasures/-e, --erasures-generation
  random|exhaustive, --erased (repeated chunk ids), --verbose/-v.
- run() -> encode() | decode(); the reference prints
  "<elapsed seconds>\t<total KiB processed>" — same here (plus --json).

TPU-native extensions (no reference analogue — the reference processes one
stripe per call on the CPU; batching stripes into HBM is this framework's
core performance primitive, SURVEY.md §2.3):
- --batch B        process B stripes of --size bytes per encode call
                   (total bytes per iteration = B * size).
- --device host|jax
                   host = numpy reference region ops (the CPU baseline);
                   jax = batched XLA/Pallas path on the default backend
                   (TPU when present). Default: jax.
- --resident       keep data resident in HBM across iterations (kernel-only
                   timing; default includes host->HBM staging + parity
                   fetch-back each iteration, the honest PCIe-inclusive
                   number).
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from typing import Dict, List

import numpy as np

from ..codes.registry import ErasureCodePluginRegistry
from ..telemetry import LatencyHistogram


class _LatTimer:
    """Per-call latency recorder for the timed benchmark loops: wraps
    each timed call in a perf_counter pair feeding a log-bucketed
    histogram, so every workload row reports p50/p99/p999 alongside
    its GB/s (metric_version 3).  One sample = one timed call — a
    stripe-batch for the iteration loops, the whole chained dispatch
    for --loop mode (which is a single device call by design)."""

    def __init__(self) -> None:
        self.hist = LatencyHistogram()

    def run(self, fn):
        t0 = time.perf_counter()
        out = fn()
        self.hist.record(time.perf_counter() - t0)
        return out

    def record(self, seconds: float) -> None:
        self.hist.record(seconds)


def _parse_parameters(params: List[str]) -> Dict[str, str]:
    profile: Dict[str, str] = {}
    for p in params:
        if "=" not in p:
            raise ValueError(f"--parameter {p!r} must be name=value")
        name, value = p.split("=", 1)
        profile[name] = value
    return profile


def build_chain(op, chain: str, packed: bool, full_init_of, reps: int):
    """The ONE chained-scan harness shared by the encode path, the
    decode path, and tools/roofline.py's kernel/harness probes (so the
    roofline numbers and the bench numbers are the same computation by
    construction).

    op: slab -> output (encode or decode step).
    chain='carry': XOR-fold full outputs into the scan carry
    (full_init_of(slabs) supplies the zero carry) — adds 3
    output-sized HBM streams per step.  chain='slice': carry one
    element (outputs 4-dim when packed, 3-dim otherwise), so the
    chain's traffic is exactly the op's own read+write; only valid
    when op is opaque to XLA DCE (a Pallas call) — a pure-XLA op would
    be narrowed to the sliced element and the number would be fiction.
    """
    import jax
    import jax.numpy as jnp

    if chain == "slice":
        def step(carry, slab):
            out = op(slab)
            sl = out[:1, :1, :1, :1] if packed else out[:1, :1, :1]
            return carry ^ sl.reshape(()), None

        def init_of(slabs):
            return jnp.zeros((), slabs.dtype)
    else:
        def step(carry, slab):
            return carry ^ op(slab), None

        init_of = full_init_of

    @jax.jit
    def chained(slabs):
        def rep(carry, _):
            c, _ = jax.lax.scan(step, carry, slabs)
            return c, None

        out, _ = jax.lax.scan(rep, init_of(slabs), None, length=reps)
        return out

    return chained


class ErasureCodeBench:
    """Benchmark driver (ceph_erasure_code_benchmark.cc -> ErasureCodeBench)."""

    def __init__(self) -> None:
        self.args = None
        self.profile: Dict[str, str] = {}

    # -- setup (ceph_erasure_code_benchmark.cc -> ErasureCodeBench::setup) --

    def setup(self, argv: List[str]) -> None:
        ap = argparse.ArgumentParser(
            prog="ceph_erasure_code_benchmark",
            description="erasure code benchmark (reference-CLI-compatible)")
        ap.add_argument("-p", "--plugin", default="jerasure",
                        help="erasure code plugin name")
        ap.add_argument("-w", "--workload", default="encode",
                        choices=["encode", "decode", "degraded",
                                 "repair-batched", "recovery-churn",
                                 "serving", "multichip", "cluster",
                                 "profile", "scenario",
                                 "tenant-week",
                                 "device-chaos", "host-chaos",
                                 "autotune"])
        ap.add_argument("-i", "--iterations", type=int, default=1)
        ap.add_argument("-s", "--size", type=int, default=1 << 20,
                        help="object size (bytes) per stripe")
        ap.add_argument("-P", "--parameter", action="append", default=[],
                        help="profile parameter name=value (repeatable)")
        ap.add_argument("-e", "--erasures", type=int, default=1,
                        help="number of chunks to erase "
                             "(decode/degraded workloads)")
        ap.add_argument("--corruptions", type=int, default=0,
                        help="shards to bit-flip per iteration "
                             "(degraded workload: scrub must detect "
                             "them, then repair treats them as "
                             "erasures)")
        ap.add_argument("--churn-every", type=int, default=2,
                        metavar="K",
                        help="recovery-churn workload: a seeded "
                             "MapChurn fires one mark_down/out/"
                             "reweight epoch every K pattern-batch "
                             "dispatches (0 disables churn — the "
                             "still-map control number)")
        ap.add_argument("--requests", type=int, default=256,
                        help="serving workload: requests in the "
                             "seeded mixed stream (the canonical "
                             "rs/shec/clay mix — --plugin/-P do not "
                             "apply to this workload)")
        ap.add_argument("--concurrency", type=int, default=64,
                        help="serving workload: closed-loop in-flight "
                             "window")
        ap.add_argument("--osds", type=int, default=1000,
                        help="cluster workload: synthetic cluster "
                             "device count (ClusterSpec.sized; "
                             "--device host downscales to keep the "
                             "host mapper in seconds)")
        ap.add_argument("--cluster-pgs", type=int, default=1024,
                        help="cluster workload: replicated pool "
                             "pg_num (the EC pool rides at 1/8)")
        ap.add_argument("--storm-events", type=int, default=40,
                        help="cluster workload: MapChurn storm epoch "
                             "budget")
        ap.add_argument("--redundancy", type=int, default=2,
                        help="cluster workload: rateless over-"
                             "planning factor r (1 = no over-"
                             "planning, the straggler-exposed "
                             "control)")
        ap.add_argument("--slow-factor", type=float, default=10.0,
                        help="cluster/scenario workloads: the "
                             "injected straggler's slowdown on "
                             "shard 0")
        ap.add_argument("--no-arbiter", action="store_true",
                        help="scenario workload: disable the mClock "
                             "QoS arbiter (the contention control "
                             "run)")
        ap.add_argument("--tune-table", default=None, metavar="FILE",
                        help="install this best-config table "
                             "(tools/autotune.py output) before the "
                             "workload — rows then report "
                             "config_source=tuned; stale/mismatched "
                             "entries fall back to defaults "
                             "byte-identically (docs/PERF.md "
                             "'Roofline-closing autotuner')")
        ap.add_argument("-E", "--erasures-generation", default="random",
                        choices=["random", "exhaustive"], dest="erasures_generation")
        ap.add_argument("--erased", action="append", type=int, default=None,
                        help="explicit chunk id to erase (repeatable)")
        ap.add_argument("-v", "--verbose", action="store_true")
        # TPU-native extensions
        ap.add_argument("--batch", type=int, default=1,
                        help="stripes per call (TPU batching extension)")
        ap.add_argument("--device", default="jax", choices=["host", "jax"])
        ap.add_argument("--resident", action="store_true",
                        help="keep data in HBM across iterations")
        ap.add_argument("--loop", type=int, default=0, metavar="N",
                        help="run N chained encodes inside ONE jitted "
                             "dispatch (lax.scan over N distinct slabs); "
                             "measures device kernel+HBM throughput with "
                             "per-dispatch latency amortized away")
        ap.add_argument("--layout", default="bytes",
                        choices=["bytes", "packed"],
                        help="device data layout for the --loop encode/"
                             "decode chains: 'packed' keeps stripes as "
                             "uint32 SWAR words end to end (the "
                             "resident layout, SURVEY §7; same bytes, "
                             "zero repacking inside the chain; w=8 "
                             "matrix codes only)")
        ap.add_argument("--chain", default="carry",
                        choices=["carry", "slice"],
                        help="--loop chain linkage: 'carry' XOR-folds "
                             "each step's full output into the scan "
                             "carry (adds 3 output-sized HBM streams "
                             "per step — the conservative pre-r05 "
                             "shape); 'slice' carries one element per "
                             "step, so the chain's HBM traffic is "
                             "exactly the op's own read+write (the "
                             "roofline-honest number; the Pallas call "
                             "is opaque to XLA DCE, so every step "
                             "still runs in full — tools/roofline.py)")
        ap.add_argument("--json", action="store_true", dest="json_out")
        ap.add_argument("--dump-perf", action="store_true",
                        help="print the perf-counter registry (perf "
                             "dump role) to stderr after the run")
        ap.add_argument("--profile-dir", default=None,
                        help="record a jax.profiler device trace here")
        ap.add_argument("--hosts", type=int, default=2,
                        help="simulated host fault domains the "
                             "host-chaos workload spans the plane "
                             "over (clamped to what the visible "
                             "devices can halve into)")
        ap.add_argument("--seed", type=int, default=42)
        self.args = ap.parse_args(argv)
        if self.args.iterations < 1:
            ap.error(f"--iterations {self.args.iterations} must be >= 1")
        if self.args.batch < 1:
            ap.error(f"--batch {self.args.batch} must be >= 1")
        if self.args.requests < 1:
            ap.error(f"--requests {self.args.requests} must be >= 1")
        if self.args.concurrency < 1:
            ap.error(f"--concurrency {self.args.concurrency} "
                     f"must be >= 1")
        if self.args.layout == "packed" and not (
                self.args.loop and self.args.device == "jax"):
            ap.error("--layout packed applies to the --loop "
                     "--device jax paths only")
        self.profile = _parse_parameters(self.args.parameter)

    # -- helpers ------------------------------------------------------------

    def _check_slice_chain(self, packed: bool) -> None:
        """--chain slice is only honest when the chained step is a
        Pallas call (opaque to XLA DCE): the packed layout on a TPU
        backend.  Anywhere else XLA narrows the op to the one sliced
        element and the printed GB/s is fiction — fail loudly instead
        (found in review: shec/clay decode and CPU runs silently
        inflated)."""
        if self.args.chain != "slice":
            return
        from ceph_tpu.ops.pallas_gf import use_pallas
        if not (packed and use_pallas()):
            raise SystemExit(
                "--chain slice requires --layout packed on a TPU "
                "backend (the Pallas step is opaque to XLA DCE); this "
                "config would lower to pure XLA and report a "
                "DCE-inflated number — use --chain carry")

    def _check_packed(self, ec) -> None:
        """--layout packed needs a coherent w=8 packed method pair;
        fail as a clean CLI error before any expensive warmup.  Two
        ways to qualify: the plugin defines its OWN packed method
        (shec's plan decode, clay/lrc's composite paths — the unified
        decode engine), or it inherits the mixin pair unshadowed (a
        plugin overriding the bytes-layout jax method while inheriting
        the mixin packed one would have the packed path bypass its
        semantics — still rejected)."""
        from ..codes.techniques import MatrixCodeMixin
        attr = ("encode_chunks_packed_jax"
                if self.args.workload == "encode"
                else "decode_chunks_packed_jax")
        base_attr = attr.replace("_packed", "")
        own_packed = (getattr(type(ec), attr, None)
                      is not getattr(MatrixCodeMixin, attr, None))
        mixin_pair = (getattr(type(ec), base_attr, None)
                      is getattr(MatrixCodeMixin, base_attr, None))
        ok = (hasattr(ec, attr)
              and getattr(ec, "w", None) == 8
              and (own_packed or mixin_pair))
        if not ok:
            raise SystemExit(
                f"ceph_erasure_code_benchmark: error: --layout packed "
                f"is not supported by plugin {self.args.plugin!r} with "
                f"this profile (w=8 matrix codes only)")

    def _decode_step_engine(self, ec, available, pat, packed):
        """Best-effort compute tier the packed decode step will route
        to (None = unknown/small): keeps --chain slice honest now that
        large composite matrices ride the MXU — a bit-sliced einsum is
        pure XLA, NOT opaque to DCE, so a slice chain over it would
        report fiction (the same failure mode the Pallas-only gate
        catches for non-packed configs)."""
        if not packed:
            return None
        comp = getattr(ec, "_decode_composite", None)
        if comp is None:
            return None
        from ceph_tpu.ops.pallas_gf import select_matrix_engine
        try:
            _, ms = comp(tuple(available), tuple(pat))
        except Exception:  # noqa: BLE001 - advisory probe only
            return None
        return select_matrix_engine((1, len(ms[0]), 1, 128), ms, 8,
                                    packed=True)

    @staticmethod
    def _decode_matrix_static(ec, available, pat):
        """The static composite/plan decode matrix the (available,
        erased) pattern actually runs, across the plugin families:
        clay/lrc probed composites, shec's minimum-read plan matrix,
        the mixin decode matrix.  None when the plugin has no matrix
        surface (bitmatrix techniques)."""
        available, pat = tuple(available), tuple(pat)
        comp = getattr(ec, "_decode_composite", None)
        if comp is not None:
            try:
                return comp(available, pat)[1]
            except Exception:  # noqa: BLE001 - advisory probe only
                return None
        tcache = getattr(ec, "tcache", None)
        if tcache is not None and hasattr(ec, "_plan_static"):  # shec
            try:
                plan = tcache.get_plan(ec.matrix, ec.k, ec.w,
                                       frozenset(available),
                                       frozenset(pat))
                return ec._plan_static(plan)[1]
            except Exception:  # noqa: BLE001 - advisory probe only
                return None
        dm = getattr(ec, "_decode_matrix", None)
        if dm is not None:
            try:
                return dm(available, pat)[1]
            except Exception:  # noqa: BLE001 - advisory probe only
                return None
        return None

    def _decode_row_meta(self, ec, available, pat, packed: bool) -> dict:
        """metric_version 9 decode-row provenance: which engine tier
        the decode matrix routes to and, when the XOR-density probe
        schedules it, the schedule stats (length, xor_ops vs dense
        gf_ops, reduction ratio) — so the bench line records WHY a
        number moved, not just that it did.  --device host rows pin
        engine="numpy" without touching jax (select_matrix_engine is a
        pure function under an explicit engine override)."""
        if getattr(ec, "w", 8) != 8:
            return {"engine": "xla", "xor_schedule": None}
        ms = self._decode_matrix_static(ec, available, pat)
        if ms is None:
            return {"engine": None, "xor_schedule": None}
        from ceph_tpu.ops.pallas_gf import select_matrix_engine
        from ceph_tpu.ops.xor_schedule import probe_schedule
        chunk = ec.get_chunk_size(self.args.size)
        cols = len(ms[0])
        if packed:
            shape = (self.args.batch, cols, max(1, chunk // 512), 128)
        else:
            shape = (self.args.batch, cols, chunk)
        override = "numpy" if self.args.device == "host" else None
        eng = select_matrix_engine(shape, ms, 8, packed=packed,
                                   engine=override, mesh=0)
        sched = probe_schedule(ms, 8)
        return {"engine": eng,
                "xor_schedule": sched.stats() if sched else None}

    def _instance(self):
        registry = ErasureCodePluginRegistry.instance()
        ec = registry.factory(self.args.plugin, dict(self.profile))
        if self.args.device == "host":
            # pin the numpy reference path: without this, batches over
            # min_xla_bytes would dispatch to XLA on the default backend
            # and the "CPU baseline" would not be a CPU baseline
            ec.min_xla_bytes = float("inf")
        return ec

    def _make_batch(self, ec) -> np.ndarray:
        """(batch, k, chunk_size) uint8 of random stripes."""
        a = self.args
        k = ec.get_data_chunk_count()
        chunk_size = ec.get_chunk_size(a.size)
        rng = np.random.default_rng(a.seed)
        data = rng.integers(0, 256, size=(a.batch, k, chunk_size),
                            dtype=np.uint8)
        return data

    # -- encode (ceph_erasure_code_benchmark.cc -> encode()) ---------------

    def encode(self) -> dict:
        a = self.args
        ec = self._instance()
        data = self._make_batch(ec)
        in_bytes_per_iter = data.nbytes  # batch * k * chunk_size
        lat = _LatTimer()

        if a.device == "host":
            ec.encode_chunks_batch(data)  # warm caches
            begin = time.perf_counter()
            for _ in range(a.iterations):
                lat.run(lambda: ec.encode_chunks_batch(data))
            elapsed = time.perf_counter() - begin
        else:
            # a tiny fetch from the last output is the completion
            # barrier (queue ordering guarantees all prior dispatches
            # are done); its ~fixed latency is amortized over the
            # iteration count.
            import jax
            if a.loop:
                import jax.numpy as jnp
                # S distinct pre-materialized slabs (so XLA can neither
                # hoist the encode out of the scan nor CSE steps); slab
                # generation happens before the timer starts
                n_slabs = min(a.loop, 16)
                reps = -(-a.loop // n_slabs)
                packed = a.layout == "packed"
                self._check_slice_chain(packed)
                if packed:
                    self._check_packed(ec)
                    from ceph_tpu.ops.pallas_gf import pack_chunks
                    staged = jax.device_put(pack_chunks(data))
                    iota = jnp.arange(n_slabs, dtype=jnp.uint32)[
                        :, None, None, None, None]
                    encode_step = ec.encode_chunks_packed_jax
                else:
                    staged = jax.device_put(data)
                    iota = jnp.arange(n_slabs, dtype=jnp.uint8)[
                        :, None, None, None]
                    encode_step = ec.encode_chunks_jax
                gen = jax.jit(lambda d: d[None] ^ iota)
                slabs = gen(staged)
                np.asarray(slabs.ravel()[:4])  # materialize

                m_ = ec.get_coding_chunk_count()

                def full_init(slabs):
                    return jnp.zeros((slabs.shape[1], m_)
                                     + slabs.shape[3:], slabs.dtype)

                chained = build_chain(encode_step, a.chain, packed,
                                      full_init, reps)

                out = chained(slabs)  # compile/warmup
                np.asarray(out.ravel()[:4])
                begin = time.perf_counter()
                out = chained(slabs)
                np.asarray(out.ravel()[:4])  # completion barrier
                elapsed = time.perf_counter() - begin
                lat.record(elapsed)  # --loop is ONE chained dispatch
                total_bytes = in_bytes_per_iter * n_slabs * reps
                return self._result("encode", elapsed, total_bytes, lat)
            if a.resident:
                dev_data = jax.device_put(data)
                out = ec.encode_chunks_jax(dev_data)  # compile/warmup
                np.asarray(out[0, 0, :4])
                begin = time.perf_counter()
                for _ in range(a.iterations):
                    # per-iteration samples are ENQUEUE latency here
                    # (the completion barrier is one fetch at the end)
                    out = lat.run(
                        lambda: ec.encode_chunks_jax(dev_data))
                np.asarray(out[0, 0, :4])  # completion barrier
                elapsed = time.perf_counter() - begin
            else:
                def run():
                    d = jax.device_put(data)
                    return np.asarray(ec.encode_chunks_jax(d))
                run()  # compile/warmup outside the timed loop
                begin = time.perf_counter()
                for _ in range(a.iterations):
                    lat.run(run)
                elapsed = time.perf_counter() - begin
        total_bytes = in_bytes_per_iter * a.iterations
        return self._result("encode", elapsed, total_bytes, lat)

    # -- decode (ceph_erasure_code_benchmark.cc -> decode()) ---------------

    def _erasure_patterns(self, ec, n: int) -> List[tuple]:
        """Sequence of erased-chunk tuples, one per iteration.

        Mirrors the reference: --erased pins an explicit set; exhaustive
        cycles all C(n, erasures) combinations; random draws per
        iteration.  Patterns the code cannot decode (possible for
        non-MDS codes like lrc/shec) are skipped, like the reference's
        decode() error-continue."""
        a = self.args

        def decodable(pat: tuple) -> bool:
            try:
                ec.minimum_to_decode(set(pat),
                                     set(range(n)) - set(pat))
                return True
            except IOError:
                return False

        if a.erasures > n:
            raise ValueError(
                f"--erasures {a.erasures} exceeds chunk count {n}")
        if a.erased:
            return [tuple(sorted(a.erased))] * a.iterations
        if a.erasures_generation == "exhaustive":
            combos = [c for c in
                      itertools.combinations(range(n), a.erasures)
                      if decodable(c)]
            if not combos:
                raise ValueError(
                    f"no decodable {a.erasures}-erasure pattern")
            reps = (a.iterations + len(combos) - 1) // len(combos)
            return (combos * reps)[:a.iterations]
        rng = np.random.default_rng(a.seed + 1)
        out: List[tuple] = []
        attempts = 0
        while len(out) < a.iterations:
            pat = tuple(sorted(rng.choice(n, size=a.erasures,
                                          replace=False)))
            attempts += 1
            if decodable(pat):
                out.append(pat)
            elif attempts > 100 * a.iterations:
                raise ValueError(
                    f"could not draw decodable {a.erasures}-erasure "
                    f"patterns")
        return out

    def _place_chunks(self, ec, data: np.ndarray,
                      parity: np.ndarray) -> np.ndarray:
        """(B, n, C) with data at get_chunk_mapping() positions (lrc
        scatters data; every other plugin is identity)."""
        n = ec.get_chunk_count()
        mapping = ec.get_chunk_mapping()
        data_pos = list(mapping) if mapping else list(range(data.shape[1]))
        parity_pos = [p for p in range(n) if p not in set(data_pos)]
        allchunks = np.empty((data.shape[0], n, data.shape[2]), np.uint8)
        allchunks[:, data_pos] = data
        allchunks[:, parity_pos] = parity
        return allchunks

    def decode(self) -> dict:
        a = self.args
        ec = self._instance()
        n = ec.get_chunk_count()
        data = self._make_batch(ec)
        parity = np.asarray(ec.encode_chunks_batch(data))
        allchunks = self._place_chunks(ec, data, parity)
        patterns = self._erasure_patterns(ec, n)
        lat = _LatTimer()

        if a.device == "jax" and a.loop:
            # device decode throughput: N chained decodes of one fixed
            # erasure pattern inside a single dispatch (mirror of the
            # encode --loop mode; slabs pre-materialized, XOR-distinct
            # so nothing hoists or CSEs)
            import jax
            import jax.numpy as jnp
            pat = patterns[0]
            available = tuple(i for i in range(n) if i not in pat)
            n_slabs = min(a.loop, 8)
            reps = -(-a.loop // n_slabs)
            avail_idx = np.array(available)
            packed = a.layout == "packed"
            self._check_slice_chain(packed)
            if a.chain == "slice" and self._decode_step_engine(
                    ec, available, pat, packed) == "mxu":
                raise SystemExit(
                    "--chain slice is dishonest for this config: the "
                    "composite decode matrix routes to the MXU einsum "
                    "(pure XLA, not opaque to DCE) — use --chain carry")
            if packed:
                self._check_packed(ec)
                from ceph_tpu.ops.pallas_gf import pack_chunks
                staged = jax.device_put(pack_chunks(allchunks))
                iota = jnp.arange(n_slabs, dtype=jnp.uint32)[
                    :, None, None, None, None]
                decode_step = ec.decode_chunks_packed_jax
            else:
                staged = jax.device_put(allchunks)
                iota = jnp.arange(n_slabs, dtype=jnp.uint8)[
                    :, None, None, None]
                decode_step = ec.decode_chunks_jax
            gen = jax.jit(lambda d: (d[None] ^ iota)[:, :, avail_idx])
            slabs = gen(staged)
            np.asarray(slabs.ravel()[:4])  # materialize

            def full_init(slabs):
                return jnp.zeros((allchunks.shape[0], len(pat))
                                 + slabs.shape[3:], slabs.dtype)

            chained = build_chain(
                lambda slab: decode_step(slab, available, pat),
                a.chain, packed, full_init, reps)

            out = chained(slabs)
            np.asarray(out.ravel()[:4])
            begin = time.perf_counter()
            out = chained(slabs)
            np.asarray(out.ravel()[:4])
            elapsed = time.perf_counter() - begin
            lat.record(elapsed)  # --loop is ONE chained dispatch
            total_bytes = data.nbytes * n_slabs * reps
            res = self._result("decode", elapsed, total_bytes, lat)
            res.update(self._decode_row_meta(ec, available, pat, packed))
            return res
        if a.device == "jax":
            import jax
            dev = jax.device_put(allchunks)
            # warmup every distinct pattern (compile outside the timed loop)
            for pat in sorted(set(patterns)):
                available = tuple(i for i in range(n) if i not in pat)
                out = ec.decode_chunks_jax(dev[:, np.array(available), :],
                                           available, pat)
            np.asarray(out[0, 0, :4])
            begin = time.perf_counter()
            for pat in patterns:
                available = tuple(i for i in range(n) if i not in pat)
                # per-pattern samples are enqueue latency (one fetch
                # barrier at the end)
                out = lat.run(lambda: ec.decode_chunks_jax(
                    dev[:, np.array(available), :], available, pat))
            np.asarray(out[0, 0, :4])  # completion barrier
            elapsed = time.perf_counter() - begin
        else:
            for pat in sorted(set(patterns)):  # warm decode-matrix caches
                available = tuple(i for i in range(n) if i not in pat)
                ec.decode_chunks_batch(
                    np.ascontiguousarray(allchunks[:, available, :]),
                    available, pat)
            begin = time.perf_counter()
            for pat in patterns:
                available = tuple(i for i in range(n) if i not in pat)
                survivors = np.ascontiguousarray(allchunks[:, available, :])
                lat.run(lambda: ec.decode_chunks_batch(
                    survivors, available, pat))
            elapsed = time.perf_counter() - begin
        total_bytes = data.nbytes * a.iterations
        res = self._result("decode", elapsed, total_bytes, lat)
        pat0 = patterns[0]
        res.update(self._decode_row_meta(
            ec, tuple(i for i in range(n) if i not in pat0), pat0,
            packed=False))
        return res

    # -- output -------------------------------------------------------------

    def _topology(self) -> dict:
        """Device topology for the row's JSON line (ISSUE 8): which
        hardware actually ran this number, so a host row can never be
        mistaken for a device run.  --device host rows report a null
        device without touching jax."""
        topo = {"platform": None, "device_kind": None,
                "device_count": 0, "mesh_shape": None}
        if self.args.device != "jax":
            return topo
        import jax
        devs = jax.devices()
        topo["platform"] = devs[0].platform
        topo["device_kind"] = devs[0].device_kind
        topo["device_count"] = len(devs)
        from ..parallel.plane import plane_topology
        topo["mesh_shape"] = plane_topology()
        return topo

    def _result(self, workload: str, elapsed: float, total_bytes: int,
                lat: "_LatTimer | None" = None) -> dict:
        gbps = total_bytes / elapsed / 1e9 if elapsed > 0 else float("inf")
        # metric_version 11: every workload row is config-provenanced
        # — which config regime (tuned best-config table vs the
        # hand-picked defaults) produced this number, and the table's
        # content hash so two tuned rows are comparable only when
        # their tables match (ceph_tpu/tune/table.py)
        from ..tune.table import active_source
        config_source, tune_key_hash = active_source()
        res = {
            "workload": workload,
            "plugin": self.args.plugin,
            "profile": dict(self.profile),
            "seconds": elapsed,
            "total_bytes": total_bytes,
            "batch": self.args.batch,
            "iterations": self.args.iterations,
            "size": self.args.size,
            "device": self.args.device,
            "layout": getattr(self.args, "layout", "bytes"),
            "chain": getattr(self.args, "chain", "carry"),
            "loop": getattr(self.args, "loop", 0),
            "gbps": gbps,
            "config_source": config_source,
            "tune_key_hash": tune_key_hash,
            **self._topology(),
        }
        if lat is not None and lat.hist.count:
            pcts = lat.hist.percentiles()
            res["lat_p50_ms"] = pcts["p50"] * 1e3
            res["lat_p99_ms"] = pcts["p99"] * 1e3
            res["lat_p999_ms"] = pcts["p999"] * 1e3
            res["lat_samples"] = lat.hist.count
        return res

    def run(self) -> dict:
        from ..utils.perf import global_perf, profile_trace
        if self.args.tune_table:
            # install the persisted best-config table BEFORE the
            # workload builds any program (the consultation seams read
            # it at build time); stays installed for the process —
            # that is the point of --tune-table
            from ..tune.table import BestConfigTable, install_table
            install_table(BestConfigTable.load(self.args.tune_table))
        with profile_trace(self.args.profile_dir):
            res = self._run_workload()
        if self.args.dump_perf:
            import json as _json
            import sys as _sys
            print(_json.dumps(global_perf().dump()), file=_sys.stderr)
        return res

    # -- degraded (recovery path: no reference analogue — the scrub →
    # repair loop timed as a workload, ISSUE 2 / docs/ROBUSTNESS.md) ----

    def degraded(self) -> dict:
        """Recovery-path throughput: deep_scrub (vectorized crc verify +
        classify) + repair (decode, re-encode, crc re-verify) of an
        object with --erasures shards erased and --corruptions shards
        bit-flipped.  Fault injection and store setup run OUTSIDE the
        timer; GB/s is logical object bytes / elapsed — the
        client-visible recovery bandwidth.  With -e 0 and no
        corruptions this times the pure deep-scrub verify pass."""
        from ..chaos import BitFlip, ShardErasure, inject
        from ..codes.stripe import HashInfo, StripeInfo
        from ..codes.stripe import encode as stripe_encode
        from ..scrub import repair
        a = self.args
        ec = self._instance()
        n = ec.get_chunk_count()
        k = ec.get_data_chunk_count()
        if a.erasures < 0 or a.corruptions < 0:
            raise ValueError("--erasures/--corruptions must be >= 0")
        if a.erasures + a.corruptions >= n:
            raise ValueError(
                f"{a.erasures} erasures + {a.corruptions} corruptions "
                f"leave no clean shards of {n}")
        chunk_size = ec.get_chunk_size(a.size)
        width = k * chunk_size
        sinfo = StripeInfo(k, width)
        rng = np.random.default_rng(a.seed)
        obj = rng.integers(0, 256, size=width * a.batch,
                           dtype=np.uint8).tobytes()
        shards = stripe_encode(sinfo, ec, obj)
        hinfo = HashInfo(n)
        hinfo.append(0, shards)

        def make_store(it: int):
            # deterministic per-iteration victim pattern; repair heals
            # the store in place, so every timed pass gets a fresh one
            prng = np.random.default_rng(a.seed + 1000 * it)
            victims = prng.choice(n, size=a.erasures + a.corruptions,
                                  replace=False)
            injectors = []
            erased = [int(v) for v in victims[:a.erasures]]
            flipped = [int(v) for v in victims[a.erasures:]]
            if erased:
                injectors.append(ShardErasure(shards=erased))
            if flipped:
                injectors.append(BitFlip(shards=flipped, flips=1))
            store, _ = inject(shards, injectors, seed=a.seed + it,
                              chunk_size=sinfo.chunk_size)
            return store

        # warm every per-pattern decode-matrix cache outside the timer
        # (mirrors the decode workload's warmup-per-distinct-pattern)
        for it in range(a.iterations):
            repair(sinfo, ec, make_store(it), hinfo)
        stores = [make_store(it) for it in range(a.iterations)]
        lat = _LatTimer()
        begin = time.perf_counter()
        for store in stores:
            lat.run(lambda: repair(sinfo, ec, store, hinfo))
        elapsed = time.perf_counter() - begin
        res = self._result("degraded", elapsed, len(obj) * a.iterations,
                           lat)
        res["erasures"] = a.erasures
        res["corruptions"] = a.corruptions
        return res

    # -- repair-batched (the unified engine's batched scrub repair:
    # one fused decode→re-encode device call per erasure-pattern
    # batch — scrub/deep_scrub.py::repair_batched) ----------------------

    def repair_batched(self) -> dict:
        """Batched recovery-path throughput: --batch objects of --size
        logical bytes each, --erasures/--corruptions faults per
        object, repaired through repair_batched (deep_scrub host CRC +
        grouped fused device repair).  GB/s is logical object bytes /
        elapsed; the result carries the pattern-batch and device-call
        counts so every round's artifact shows the batching held
        (pattern_batches == device_calls, not one call per object)."""
        from ..chaos import BitFlip, ShardErasure, inject
        from ..codes.stripe import HashInfo, StripeInfo
        from ..codes.stripe import encode as stripe_encode
        from ..scrub import repair_batched
        a = self.args
        ec = self._instance()
        n = ec.get_chunk_count()
        k = ec.get_data_chunk_count()
        if a.erasures < 0 or a.corruptions < 0:
            raise ValueError("--erasures/--corruptions must be >= 0")
        if a.erasures + a.corruptions >= n:
            raise ValueError(
                f"{a.erasures} erasures + {a.corruptions} corruptions "
                f"leave no clean shards of {n}")
        chunk_size = ec.get_chunk_size(a.size)
        width = k * chunk_size
        sinfo = StripeInfo(k, width)
        rng = np.random.default_rng(a.seed)
        objects = []
        for i in range(a.batch):
            obj = rng.integers(0, 256, size=width,
                               dtype=np.uint8).tobytes()
            shards = stripe_encode(sinfo, ec, obj)
            hinfo = HashInfo(n)
            hinfo.append(0, shards)
            objects.append((shards, hinfo))
        hinfos = [h for _, h in objects]

        # a small pool of fault patterns cycled across objects, so the
        # sweep exercises the grouping (a few patterns, many objects)
        prng = np.random.default_rng(a.seed + 1)
        n_pat = max(1, min(4, a.batch))
        pool = []
        for _ in range(n_pat):
            victims = prng.choice(n, size=a.erasures + a.corruptions,
                                  replace=False)
            pool.append(([int(v) for v in victims[:a.erasures]],
                         [int(v) for v in victims[a.erasures:]]))

        def make_stores():
            stores = []
            for i, (shards, _) in enumerate(objects):
                erased, flipped = pool[i % n_pat]
                injectors = []
                if erased:
                    injectors.append(ShardErasure(shards=erased))
                if flipped:
                    injectors.append(BitFlip(shards=flipped, flips=1))
                store, _ = inject(shards, injectors, seed=a.seed + i,
                                  chunk_size=sinfo.chunk_size)
                stores.append(store)
            return stores

        # --device host pins the grouped HOST path (zero jax work —
        # _instance() already pinned min_xla_bytes, so the plugin
        # batch calls stay on numpy too)
        dev = a.device != "host"
        # warm pattern caches + jit traces outside the timer
        repair_batched(sinfo, ec, make_stores(), hinfos, device=dev)
        runs = [make_stores() for _ in range(a.iterations)]
        lat = _LatTimer()
        begin = time.perf_counter()
        rep = None
        for stores in runs:
            rep = lat.run(lambda: repair_batched(sinfo, ec, stores,
                                                 hinfos, device=dev))
        elapsed = time.perf_counter() - begin
        res = self._result("repair-batched", elapsed,
                           width * a.batch * a.iterations, lat)
        res["erasures"] = a.erasures
        res["corruptions"] = a.corruptions
        res["pattern_batches"] = rep.pattern_batches
        res["device_calls"] = rep.device_calls
        res["host_batches"] = rep.host_batches
        return res

    # -- recovery-churn (the epoch-aware orchestrator under live map
    # churn: repair throughput while a seeded MapChurn advances the
    # OSDMap between pattern-batch dispatches — recovery/ + ISSUE 4) --

    def recovery_churn(self) -> dict:
        """Recovery throughput under OSDMap churn: --batch objects of
        --size logical bytes, --erasures/--corruptions faults each,
        driven to durable convergence by the recovery orchestrator
        (epoch fencing + intent journal + throttle) while a seeded
        MapChurn fires one epoch every --churn-every pattern-batch
        dispatches.  GB/s is logical object bytes / elapsed — the
        client-visible recovery bandwidth including every replan,
        regroup and journal pass churn forces; the result carries the
        replan/regroup counters so the fencing overhead is visible
        next to the still-map repair-batched row."""
        from ..chaos import BitFlip, MapChurn, ShardErasure, inject
        from ..codes.stripe import HashInfo, StripeInfo
        from ..codes.stripe import encode as stripe_encode
        from ..crush import (CrushBuilder, step_chooseleaf_indep,
                             step_emit, step_take)
        from ..crush.osdmap import OSDMap, PGPool
        from ..recovery import healed, recover_to_completion
        a = self.args
        ec = self._instance()
        n = ec.get_chunk_count()
        k = ec.get_data_chunk_count()
        if a.erasures < 1 or a.corruptions < 0:
            raise ValueError("recovery-churn needs --erasures >= 1")
        if a.erasures + a.corruptions >= n:
            raise ValueError(
                f"{a.erasures} erasures + {a.corruptions} corruptions "
                f"leave no clean shards of {n}")
        chunk_size = ec.get_chunk_size(a.size)
        width = k * chunk_size
        sinfo = StripeInfo(k, width)
        rng = np.random.default_rng(a.seed)
        objects = []
        for i in range(a.batch):
            obj = rng.integers(0, 256, size=width,
                               dtype=np.uint8).tobytes()
            shards = stripe_encode(sinfo, ec, obj)
            hinfo = HashInfo(n)
            hinfo.append(0, shards)
            objects.append((shards, hinfo))
        hinfos = [h for _, h in objects]

        prng = np.random.default_rng(a.seed + 1)
        n_pat = max(1, min(4, a.batch))
        pool = []
        for _ in range(n_pat):
            victims = prng.choice(n, size=a.erasures + a.corruptions,
                                  replace=False)
            pool.append(([int(v) for v in victims[:a.erasures]],
                         [int(v) for v in victims[a.erasures:]]))

        def make_cluster():
            b = CrushBuilder()
            root = b.build_two_level(n + 3, 2)
            b.add_rule(0, [step_take(root),
                           step_chooseleaf_indep(n, b.type_id("host")),
                           step_emit()])
            osdmap = OSDMap(crush=b.map)
            osdmap.pools[1] = PGPool(pool_id=1, pg_num=16, size=n,
                                     erasure=True)
            return osdmap

        def make_stores():
            stores = []
            for i, (shards, _) in enumerate(objects):
                erased, flipped = pool[i % n_pat]
                inj = []
                if erased:
                    inj.append(ShardErasure(shards=list(erased)))
                if flipped:
                    inj.append(BitFlip(shards=list(flipped), flips=1))
                st, _ = inject(shards, inj, seed=a.seed + i,
                               chunk_size=sinfo.chunk_size)
                stores.append(st)
            return stores

        dev = a.device != "host"

        def run_once(seed_off):
            # fresh map + stores each pass: churn mutates the map, so
            # a reused one would drift across iterations
            churn = (MapChurn(seed=a.seed + seed_off, max_down=1,
                              fire_every=a.churn_every,
                              stages=("dispatch",))
                     if a.churn_every else None)
            stores = make_stores()
            rep = recover_to_completion(
                sinfo, ec, make_cluster(), 1, 9, stores, hinfos,
                churn=churn, device=dev)
            if not rep.converged or rep.unrecoverable:
                raise RuntimeError(
                    f"recovery-churn failed to converge: "
                    f"{rep.to_dict()}")
            if not healed(stores, [s for s, _ in objects]):
                raise RuntimeError("recovery-churn: data loss")
            return rep

        run_once(1000)                      # warm caches + jit traces
        lat = _LatTimer()
        begin = time.perf_counter()
        rep = None
        for it in range(a.iterations):
            rep = lat.run(lambda: run_once(it))
        elapsed = time.perf_counter() - begin
        res = self._result("recovery-churn", elapsed,
                           width * a.batch * a.iterations, lat)
        res["erasures"] = a.erasures
        res["corruptions"] = a.corruptions
        res["churn_every"] = a.churn_every
        res["epochs_advanced"] = rep.epoch_end - rep.epoch_start
        res["replans"] = rep.replans
        res["regroups"] = rep.regroups
        res["rounds"] = rep.rounds
        res["pattern_batches"] = rep.pattern_batches
        res["device_calls"] = rep.device_calls
        return res

    def _run_traced(self, run_fn):
        """Run one serving/scenario measurement under a fresh causal-
        tracing collector (telemetry/tracing.py) and return
        ``(result, tail_attribution)`` — the metric_version 12 blob:
        per-segment share of p99 time across all op classes
        (telemetry/analyzer.py::tail_shares), so a serving number
        that moves names which segment moved it.  Works identically
        on the host-only error path (the seams are host bookkeeping);
        the previous collector (if any) is restored."""
        from ..telemetry import analyzer, tracing
        coll = tracing.TraceCollector(seed=self.args.seed)
        prev = tracing.install(coll)
        try:
            out = run_fn()
        finally:
            tracing.install(prev)
        rows = analyzer.decompose_all(coll.to_dict())
        return out, analyzer.tail_shares(rows, "p99")

    # -- serving (the ragged continuous-batching front-end: a seeded
    # mixed request stream through serve/ — ROADMAP item 3) -------------

    def serving(self) -> dict:
        """Tail-latency serving numbers: the canonical mixed
        rs/shec/clay stream (serve.loadgen.default_spec, seeded by
        --seed) driven closed-loop through the admission queue and the
        continuous batcher on the REAL clock.  The row reports
        GB/s-under-SLO (only bytes of requests that met their
        deadline), request-latency p50/p99/p999, deadline-miss rate
        and padding overhead — the axes offline GB/s cannot see.  The
        stream is byte-verified against the generator's ground truth
        and, on the jax path, carries the post-warmup backend-compile
        count (0 = the zero-warm-recompile contract held)."""
        from ..serve import (default_spec, run_serving_scenario,
                             verify_results)
        a = self.args
        executor = "device" if a.device == "jax" else "host"
        spec = default_spec(seed=a.seed, n_requests=a.requests,
                            stripe_size=a.size, erasures=a.erasures,
                            arrival="closed")
        spec.concurrency = a.concurrency
        run, tail = self._run_traced(
            lambda: run_serving_scenario(spec, executor=executor))
        bad = verify_results(run.results)
        if bad:
            raise RuntimeError(
                f"serving stream corrupted: {len(bad)} request(s) "
                f"differ from ground truth (ids {bad[:8]})")
        rep = run.report
        res = self._result("serving", rep["elapsed_s"], rep["bytes"])
        res["lat_p50_ms"] = rep["p50_ms"]
        res["lat_p99_ms"] = rep["p99_ms"]
        res["lat_p999_ms"] = rep["p999_ms"]
        res["lat_samples"] = rep["requests"]
        res["gbps_under_slo"] = rep["gbps_under_slo"]
        res["deadline_miss_rate"] = rep["deadline_miss_rate"]
        res["padding_overhead"] = rep["padding"]["padding_overhead"]
        res["cached_programs"] = rep["padding"].get("cached_programs")
        res["requests"] = rep["requests"]
        res["rejected"] = rep["rejected"]
        res["dispatches"] = rep["padding"]["dispatches"]
        res["stream_compiles"] = rep.get("stream_compiles")
        res["op_classes"] = rep["op_classes"]
        res["tail_attribution"] = tail
        return res

    # -- multichip (the mesh data plane: encode fanned out across the
    # device mesh through the engine's sharded tier — ISSUE 8) ----------

    def multichip(self) -> dict:
        """Mesh-sharded encode throughput: --batch stripes of --size
        bytes dispatched through the engine's sharded serving program
        (serve_dispatch_call under an active data plane spanning every
        visible device — stripe batch sharded, coding matrix
        replicated, ONE device dispatch per call).  The output is
        byte-verified against the single-device engine before timing,
        and the row carries the mesh shape + per-device stripe
        partition so host-only rounds (device_count 1) are
        self-describing.  On a single visible device the plane
        degrades to the single-device tier — the row then IS the
        single-chip number, labeled as such."""
        a = self.args
        if a.device != "jax":
            raise SystemExit(
                "ceph_erasure_code_benchmark: error: --workload "
                "multichip measures the mesh data plane; it requires "
                "--device jax")
        import jax

        from ..codes.engine import serve_dispatch_call
        from ..parallel.plane import mesh_plane, plane_topology

        ec = self._instance()
        data = self._make_batch(ec)
        # single-device reference OUTSIDE the plane (byte-identity pin)
        ref = np.asarray(
            serve_dispatch_call(ec, "encode", mesh=False)(
                jax.device_put(data)))
        lat = _LatTimer()
        with mesh_plane() as plane:
            fn = serve_dispatch_call(ec, "encode")
            staged = jax.device_put(data)
            out = fn(staged)  # compile/warmup
            np.asarray(out.ravel()[:4])
            if not np.array_equal(np.asarray(out), ref):
                raise RuntimeError(
                    "multichip: sharded encode diverged from the "
                    "single-device engine")
            begin = time.perf_counter()
            for _ in range(a.iterations):
                out = lat.run(lambda: fn(staged))
            np.asarray(out.ravel()[:4])  # completion barrier
            elapsed = time.perf_counter() - begin
            shards = sorted(s.data.shape[0]
                            for s in out.addressable_shards)
            res = self._result("multichip", elapsed,
                               data.nbytes * a.iterations, lat)
            res["mesh_shape"] = plane_topology(plane)
        res["n_devices"] = (plane.n_devices if plane is not None else 1)
        res["stripes_per_device"] = shards
        res["verified"] = True
        return res

    # -- cluster (the 10k-OSD cluster plane: storm → balance →
    # rateless recover from one seed — ceph_tpu/cluster/, ISSUE 9) -----

    def cluster(self) -> dict:
        """Cluster-plane numbers: a seeded synthetic cluster
        (--osds devices, ClusterSpec.sized) takes a --storm-events
        MapChurn storm through the incremental path (full-cluster
        remap convergence measured per epoch on the bulk evaluator,
        pinned equivalent to a rebuilt map and a catch_up replay),
        the balancer loop closes on device to max deviation <= 1,
        and a rateless first-k recovery (--redundancy copies across
        the mesh shards) heals --batch damaged objects under an
        injected straggler (shard 0 at --slow-factor), byte-verified
        and compared against the same schedule with no straggler —
        the p99 ratio IS the straggler-tolerance claim.  --device
        host runs the identical loop over the host mapper at a
        downscaled size."""
        from ..chaos import ShardErasure, Straggler
        from ..cluster import (ClusterSpec, balance_cluster,
                               build_cluster, rateless_recover,
                               run_churn_storm,
                               verify_storm_equivalence)
        from ..cluster.rateless import plan_assignments, \
            simulate_first_k
        from ..cluster.topology import EC_POOL
        from ..codes.stripe import StripeInfo
        from ..recovery import healed
        from ..scenario.runner import stage_damaged_objects
        a = self.args
        host = a.device == "host"
        # the host engine walks the python mapper per pg per epoch —
        # the downscale keeps it in seconds while running the
        # identical loop
        n_osds = min(a.osds, 120) if host else a.osds
        pgs = min(a.cluster_pgs, 128) if host else a.cluster_pgs
        events = min(a.storm_events, 6) if host else a.storm_events
        engine = "host" if host else "bulk"
        measure_every = 2 if host else 1
        spec = ClusterSpec.sized(
            n_osds, seed=a.seed, replicated_pg_num=pgs,
            ec_pg_num=max(32, pgs // 8))
        m = build_cluster(spec)

        ec = self._instance()
        n = ec.get_chunk_count()
        k = ec.get_data_chunk_count()
        chunk_size = ec.get_chunk_size(a.size)
        width = k * chunk_size
        sinfo = StripeInfo(k, width)
        n_objects = max(4, a.batch)
        # one shared erasure pattern (shard 1): one pattern batch, one
        # fused dispatch — and the control sim below can reconstruct
        # the unit work exactly.  Staging rides the shared scenario
        # runner (scenario/runner.py), same bytes as the old inline
        # loop.
        objects, stores, hinfos, _ = stage_damaged_objects(
            sinfo, ec, n_objects, seed=a.seed,
            injectors_for=lambda i: [ShardErasure(shards=[1])])

        from ..chaos import MapChurn
        churn = MapChurn(seed=a.seed + 1, max_down=8, fire_every=1,
                         max_events=events)
        lat = _LatTimer()
        begin = time.perf_counter()
        storm = lat.run(lambda: run_churn_storm(
            m, churn=churn, events=events, engine=engine,
            measure_every=measure_every))
        verify_storm_equivalence(
            m, churn, lambda: build_cluster(spec), engine=engine,
            scalar_samples=4)
        bal = lat.run(lambda: balance_cluster(
            m, max_deviation=1.0, engine=engine))
        straggler = Straggler(seed=a.seed + 2,
                              slow={0: a.slow_factor})
        rec, rr = lat.run(lambda: rateless_recover(
            sinfo, ec, m, EC_POOL, 5, stores, hinfos,
            redundancy=a.redundancy, straggler=straggler,
            seed=a.seed + 3, device=not host))
        elapsed = time.perf_counter() - begin
        if not rec.converged or rec.unrecoverable:
            raise RuntimeError(
                f"cluster: recovery failed: {rec.to_dict()}")
        if not healed(stores, objects):
            raise RuntimeError("cluster: data loss after rateless "
                               "recovery")
        # no-straggler control: the SAME plan/work simulated on a
        # clean service model — the denominator of the p99 claim
        # every object lost exactly shard 1 of chunk_size bytes, so
        # each unit's work matches rateless_recover's classification
        work = [chunk_size / float(1 << 16)] * rr.n_units
        plan = plan_assignments(rr.n_units, rr.n_shards,
                                rr.redundancy, seed=a.seed + 3)
        baseline = simulate_first_k(
            plan, Straggler(seed=a.seed + 2), work)
        import numpy as _np
        base_p99 = float(_np.percentile(
            _np.asarray(baseline.completion_s), 99)) \
            if baseline.completion_s else 0.0

        res = self._result("cluster", elapsed,
                           width * n_objects, lat)
        res["osds"] = spec.n_osds
        res["total_pgs"] = sum(p.pg_num for p in m.pools.values())
        res["engine"] = engine
        res["storm_events"] = storm.events + storm.drain_events
        res["remap_convergence_epochs"] = storm.epochs_to_quiescence
        res["remapped_total"] = storm.total_remapped
        res["mean_remap_fraction"] = round(
            storm.mean_remap_fraction, 6)
        res["balancer_iterations"] = bal.iterations
        res["balancer_moves"] = bal.moves
        res["balancer_converged"] = bal.converged
        res["balancer_max_dev_final"] = round(bal.max_dev_final, 4)
        res["balancer_remap_fraction"] = round(bal.remap_fraction, 6)
        res["redundancy"] = rr.redundancy
        res["n_shards"] = rr.n_shards
        res["p99_recovery_ms"] = round(rr.p99_s * 1e3, 4)
        res["p99_baseline_ms"] = round(base_p99 * 1e3, 4)
        res["p99_ratio"] = (round(rr.p99_s / base_p99, 4)
                            if base_p99 > 0 else None)
        res["straggler_reassignments"] = \
            rr.schedule.straggler_reassignments if rr.schedule else 0
        res["verified"] = True
        return res

    # -- scenario (the composed production day: client traffic at SLO
    # + churn storm + straggler recovery under mClock QoS arbitration
    # — ISSUE 11, ceph_tpu/scenario/, docs/SCENARIOS.md) ----------------

    def scenario_workload(self) -> dict:
        """Production-day contention numbers (metric_version 8): the
        canonical mixed rs/shec/clay client stream serves at SLO while
        a churn storm remaps the cluster, recovery rounds heal
        straggler-skewed damage and scrub verifies — all on ONE real
        clock, admission-gated by the mClock arbiter
        (scenario/qos.py; --no-arbiter is the unthrottled control).
        The contention axes — GB/s-under-SLO, p99,
        deadline-miss-rate — are what tools/bench_diff.py's
        ``scenario`` category gates; since metric_version 12 the row
        also carries ``tail_attribution`` (per-segment share of p99
        time from the causal tracing plane, telemetry/analyzer.py).
        Correctness gates run in-workload: client stream
        byte-verified against ground truth, recovery converged with
        byte-identical heal, zero data loss."""
        from ..scenario import default_scenario, run_scenario
        a = self.args
        executor = "device" if a.device == "jax" else "host"
        spec = default_scenario(
            seed=a.seed, n_requests=a.requests, stripe_size=a.size,
            damaged_objects=max(2, a.batch), erasures=a.erasures,
            storm_events=min(a.storm_events, 12),
            straggler_factor=a.slow_factor)
        run, tail = self._run_traced(
            lambda: run_scenario(spec, executor=executor,
                                 enable_arbiter=not a.no_arbiter))
        rep = run.report
        if not rep.ok():
            raise RuntimeError(f"scenario gates failed: {rep.gates}")
        res = self._result("scenario", rep.slo["elapsed_s"],
                           rep.slo["bytes"])
        res["lat_p50_ms"] = rep.slo["p50_ms"]
        res["lat_p99_ms"] = rep.slo["p99_ms"]
        res["lat_p999_ms"] = rep.slo["p999_ms"]
        res["lat_samples"] = rep.slo["requests"]
        res["gbps_under_slo"] = rep.gbps_under_slo
        res["deadline_miss_rate"] = rep.deadline_miss_rate
        res["arbiter_enabled"] = rep.arbiter_enabled
        res["qos_scale_min"] = rep.qos["scale_min"]
        res["qos_burn_trips"] = rep.qos["burn_trips"]
        res["slo_burn_trips"] = rep.slo_burn_trips
        res["recovery_rounds"] = rep.recovery_rounds
        res["recovery_ops_completed"] = \
            rep.recovery["ops_completed"]
        res["churn_events"] = rep.churn["events"]
        res["straggler_reassignments"] = \
            rep.rateless["straggler_reassignments"]
        res["rateless_p99_ratio"] = rep.rateless["p99_ratio"]
        res["stream_compiles"] = rep.slo.get("stream_compiles")
        res["tail_attribution"] = tail
        res["verified"] = True
        return res

    # -- tenant-week (the multi-tenant compressed week: per-tenant
    # diurnal streams under the per-tenant mClock door, discrete-event
    # fast-forward, staged correlated disasters — ISSUE 19,
    # ceph_tpu/scenario/week.py, docs/SCENARIOS.md) ---------------------

    def tenant_week_workload(self) -> dict:
        """Multi-tenant isolation numbers (metric_version 16): the
        pinned 3-tenant compressed week — diurnal streams merged on
        one arrival timeline, the noisy tenant's burst storm clamped
        at the door by its mClock limit tag, all four staged
        disasters healing byte-identically — runs as a discrete-event
        simulation on an EventClock (the service model charges
        modeled time, so every number is deterministic from the
        seed).  The row carries per-tenant scorecards plus the
        isolation-gate verdict against per-tenant isolated baselines;
        ``--no-arbiter`` is the control arm that must FAIL that gate.
        Correctness gates in-workload: recovery converged, heal
        byte-identical, every served request byte-verified."""
        from ..scenario import (isolated_baseline, isolation_gate,
                                run_tenant_week, tenant_week_scenario)
        a = self.args
        # the bench row runs the tiny-scale week (the full ~1e5-request
        # week is the demo's job); scale rides --iterations as days
        spec = tenant_week_scenario(
            seed=a.seed, days=max(2, a.iterations), day_s=6.0,
            peak_rates=(40.0, 30.0, 20.0), burst_factor=80.0)
        run = run_tenant_week(spec,
                              enable_arbiter=not a.no_arbiter)
        rep = run.report
        g = rep.gates
        if not (g["converged"] and g["healed"]
                and g["verified_requests"]):
            raise RuntimeError(f"tenant-week gates failed: {g}")
        victims = tuple(t.name for t in spec.tenants
                        if t.limit == 0.0)
        base = {n: isolated_baseline(spec, n) for n in victims}
        gate = isolation_gate(rep, base, victims=victims)
        if not a.no_arbiter and not gate["ok"]:
            raise RuntimeError(
                f"tenant-week isolation gate failed: {gate}")
        res = self._result("tenant-week", rep.slo["elapsed_s"],
                           rep.slo["bytes"])
        res["lat_p50_ms"] = rep.slo["p50_ms"]
        res["lat_p99_ms"] = rep.slo["p99_ms"]
        res["lat_p999_ms"] = rep.slo["p999_ms"]
        res["lat_samples"] = rep.slo["requests"]
        res["gbps_under_slo"] = rep.gbps_under_slo
        res["deadline_miss_rate"] = rep.deadline_miss_rate
        res["arbiter_enabled"] = rep.arbiter_enabled
        res["requests_offered"] = g["requests_offered"]
        res["dispatched"] = g["dispatched"]
        res["dispatch_crc"] = g["dispatch_crc"]
        res["tenants"] = rep.tenants
        # victims' GB/s-under-SLO with the burst storm raging is THE
        # isolation number (bench_diff `tenant_isolation` series)
        res["victim_gbps_under_slo"] = sum(
            (rep.tenants.get(n, {}).get("gbps_under_slo") or 0.0)
            for n in victims)
        res["isolation_ok"] = gate["ok"]
        res["isolation_victims"] = gate["victims"]
        res["disasters"] = rep.disasters
        res["disasters_healed"] = all(
            d["healed"] for d in rep.disasters)
        res["fence_deferrals"] = sum(
            d["fence_deferrals"] for d in rep.disasters)
        res["recovery_rounds"] = rep.recovery_rounds
        res["scrub_ticks"] = rep.scrub_ticks
        res["churn_events"] = rep.churn["events"]
        res["verified"] = True
        return res

    # -- profile (the device-plane profiler: per-program cost/roofline
    # attribution for the engine's cached programs — ISSUE 10,
    # telemetry/profiler.py, docs/OBSERVABILITY.md) ---------------------

    def profile_workload(self) -> dict:
        """Cost/roofline attribution workload (metric_version 7):
        drives the engine's cached programs — serve encode, serve
        decode and the fused decode→re-encode repair — for the
        configured plugin, and emits per-program attribution rows
        joining XLA ``cost_analysis`` (FLOPs, bytes accessed) with the
        measured dispatch histograms: achieved GB/s, model-bound GB/s
        at the HBM roofline, utilization %.

        ``--device host`` runs the numpy batch surfaces instead and
        fills the cost side from the analytic GF(2^8) matrix model
        (``source="analytic"``)."""
        from ..telemetry import profiler as profmod
        a = self.args
        ec = self._instance()
        n = ec.get_chunk_count()
        m_ = ec.get_coding_chunk_count()
        data = self._make_batch(ec)
        chunk_size = data.shape[2]
        pat = self._erasure_patterns(ec, n)[0]
        available = tuple(i for i in range(n) if i not in pat)
        parity = np.asarray(ec.encode_chunks_batch(data))
        allchunks = self._place_chunks(ec, data, parity)
        survivors = np.ascontiguousarray(
            allchunks[:, np.array(available), :])
        lat = _LatTimer()
        plugin_cls = type(ec).__name__

        if a.device == "jax":
            import jax

            from ..codes.engine import (fused_repair_call,
                                        serve_dispatch_call)
            prof = profmod.global_profiler()
            enc = serve_dispatch_call(ec, "encode")
            dec = serve_dispatch_call(ec, "decode", available, pat)
            rep = fused_repair_call(ec, available, pat)
            denc = jax.device_put(data)
            dsurv = jax.device_put(survivors)
            calls = [lambda: enc(denc), lambda: dec(dsurv),
                     lambda: rep(dsurv)]
            for fn in calls:            # warm: compile + cost capture
                jax.block_until_ready(fn())
            begin = time.perf_counter()
            for _ in range(a.iterations):
                for fn in calls:
                    lat.run(lambda fn=fn: jax.block_until_ready(fn()))
            elapsed = time.perf_counter() - begin
            total_bytes = (data.nbytes + 2 * survivors.nbytes) \
                * a.iterations
            rows = [r for r in prof.attribution_rows()
                    if r.get("plugin") == plugin_cls]
        else:
            # host tier: numpy batch surfaces + the analytic cost
            # model — no jax anywhere
            prof = profmod.ProgramProfiler()
            ops = [
                ("encode", m_, ec.get_data_chunk_count(),
                 lambda: ec.encode_chunks_batch(data)),
                ("decode", len(pat), len(available),
                 lambda: ec.decode_chunks_batch(survivors, available,
                                                pat)),
            ]
            for opname, rows_, cols_, fn in ops:
                key = ("bench.profile", plugin_cls, opname)
                # the analytic model extended to XOR schedules
                # (ISSUE 12): when the decode matrix the pattern
                # actually runs is XOR-scheduled, the cost side
                # carries the schedule's REAL op count (and the row
                # says engine="xor"), so host-only rounds report the
                # FLOP reduction, not the dense fiction
                cost = profmod.analytic_matrix_cost(
                    a.batch, rows_, cols_, chunk_size)
                host_engine = "host"
                ms = (self._decode_matrix_static(ec, available, pat)
                      if opname == "decode"
                      and getattr(ec, "w", 8) == 8 else None)
                if ms is not None:
                    from ..ops.xor_schedule import preferred_schedule
                    mr, mc = len(ms), len(ms[0])
                    unit = chunk_size // getattr(ec, "sub_chunk_no", 1)
                    sched = preferred_schedule(ms, 8)
                    if sched is not None:
                        cost = profmod.analytic_xor_schedule_cost(
                            a.batch, mr, mc, unit, sched.vpu_ops)
                        host_engine = "xor"
                    else:
                        cost = profmod.analytic_matrix_cost(
                            a.batch, mr, mc, unit)
                prof.capture(
                    key, name=f"host.{opname}", device_kind="cpu",
                    cost=cost,
                    arg_bytes=a.batch * cols_ * chunk_size,
                    plugin=plugin_cls, kind=f"host-{opname}",
                    pattern="e" + "_".join(map(str, pat)),
                    engine=host_engine, devices=0, batch=a.batch)
                fn()                    # warm caches
            begin = time.perf_counter()
            for _ in range(a.iterations):
                for opname, _r, _c, fn in ops:
                    key = ("bench.profile", plugin_cls, opname)
                    t0 = time.perf_counter()
                    fn()
                    dt = time.perf_counter() - t0
                    lat.record(dt)
                    prof.observe(key, dt)
            elapsed = time.perf_counter() - begin
            total_bytes = (data.nbytes + survivors.nbytes) \
                * a.iterations
            rows = prof.attribution_rows()

        res = self._result("profile", elapsed, total_bytes, lat)
        res["erasures"] = len(pat)
        res["programs"] = len(rows)
        res["profile_rows"] = rows
        return res

    # -- device-chaos (the supervised dispatch plane under injected
    # device-plane faults: recovery-under-fault throughput — ISSUE 13,
    # ops/supervisor.py + chaos/dispatch.py) ----------------------------

    def device_chaos(self) -> dict:
        """Recovery throughput while the device plane FAILS mid-run:
        --batch objects of --size logical bytes, --erasures faults
        each, repaired through the batched fused-repair seam while a
        seeded DispatchFault script (transient error, HBM OOM, then a
        persistent backend loss) fires at the seam's Nth calls.  The
        supervisor must retry, split the rung, demote the tier live
        and complete on the numpy twin — byte-identical heal and zero
        data loss are gated in-workload, and the row carries the
        supervisor counter deltas so bench_diff's ``device_chaos``
        category can never silently regress recovery-under-fault.

        ``--device host`` (the CPU baseline): the same loop wraps the
        grouped host repair in the supervisor at a bench seam, so the
        classification machinery (retry, demoted completion) is
        measured without a device."""
        from ..chaos import BitFlip, ShardErasure, inject
        from ..chaos.dispatch import (DispatchFault, DispatchFaultPlan,
                                      arm_plan)
        from ..codes.stripe import HashInfo, StripeInfo
        from ..codes.stripe import encode as stripe_encode
        from ..ops.supervisor import global_supervisor
        from ..recovery.orchestrator import healed
        from ..scrub import repair_batched
        a = self.args
        ec = self._instance()
        n = ec.get_chunk_count()
        k = ec.get_data_chunk_count()
        if a.erasures < 1 or a.erasures + a.corruptions >= n:
            raise ValueError("device-chaos needs 1 <= erasures + "
                             "corruptions < n")
        chunk_size = ec.get_chunk_size(a.size)
        width = k * chunk_size
        sinfo = StripeInfo(k, width)
        rng = np.random.default_rng(a.seed)
        objects = []
        for i in range(a.batch):
            obj = rng.integers(0, 256, size=width,
                               dtype=np.uint8).tobytes()
            shards = stripe_encode(sinfo, ec, obj)
            hinfo = HashInfo(n)
            hinfo.append(0, shards)
            objects.append((shards, hinfo))
        hinfos = [h for _, h in objects]
        originals = [s for s, _ in objects]

        prng = np.random.default_rng(a.seed + 1)
        n_pat = max(1, min(4, a.batch))
        pool = []
        for _ in range(n_pat):
            victims = prng.choice(n, size=a.erasures + a.corruptions,
                                  replace=False)
            pool.append(([int(v) for v in victims[:a.erasures]],
                         [int(v) for v in victims[a.erasures:]]))

        def make_stores():
            stores = []
            for i, (shards, _) in enumerate(objects):
                erased, flipped = pool[i % n_pat]
                inj = []
                if erased:
                    inj.append(ShardErasure(shards=list(erased)))
                if flipped:
                    inj.append(BitFlip(shards=list(flipped), flips=1))
                st, _ = inject(shards, inj, seed=a.seed + i,
                               chunk_size=sinfo.chunk_size)
                stores.append(st)
            return stores

        dev = a.device != "host"
        sup = global_supervisor()
        seam = ("engine.fused_repair" if dev
                else "bench.device_chaos")

        def fault_script():
            # the seeded production-day failure script: a flaky call,
            # an HBM OOM (device mode — the host seam fires once per
            # repair pass, so its script compresses to retry + loss),
            # then the backend dies for two calls
            if dev:
                faults = [DispatchFault("transient", seam=seam, at=2,
                                        calls=1),
                          DispatchFault("oom", seam=seam, at=3,
                                        calls=1),
                          DispatchFault("backend_loss", seam=seam,
                                        at=5, calls=2)]
            else:
                faults = [DispatchFault("transient", seam=seam, at=1,
                                        calls=1),
                          DispatchFault("backend_loss", seam=seam,
                                        at=2, calls=2)]
            return DispatchFaultPlan(faults, seed=a.seed)

        def run_once():
            stores = make_stores()
            if dev:
                rep = repair_batched(sinfo, ec, stores, hinfos,
                                     device=True)
            else:
                call = (lambda: repair_batched(
                    sinfo, ec, stores, hinfos, device=False))
                rep = sup.dispatch(seam, lambda: call(), (),
                                   host_fn=lambda: call(),
                                   splittable=False)
            if not healed(stores, originals):
                raise RuntimeError("device-chaos: data loss under "
                                   "injected dispatch faults")
            return rep

        # warm pattern caches + traces with NO faults armed
        run_once()
        before = {key: v for key, v in sup.stats().items()
                  if isinstance(v, int)}
        lat = _LatTimer()
        plans = []
        begin = time.perf_counter()
        for _ in range(a.iterations):
            plan = fault_script()
            prev = arm_plan(plan)
            try:
                lat.run(run_once)
                plan.clear()
                # drive the health probe to re-promotion so every
                # iteration starts from the healthy tier
                for _ in range(sup.promote_after + 2):
                    sup.tick()
            finally:
                arm_plan(prev)
            plans.append(plan.summary())
        elapsed = time.perf_counter() - begin
        after = sup.stats()
        res = self._result("device-chaos", elapsed,
                           width * a.batch * a.iterations, lat)
        res["erasures"] = a.erasures
        res["supervisor"] = {
            key: after[key] - before.get(key, 0)
            for key in ("retries", "rung_downshifts", "demotions",
                        "quarantines", "repromotions",
                        "host_completions", "hangs",
                        "verify_failures")}
        res["faults_fired"] = sum(p["fired"] for p in plans)
        res["demoted_at_end"] = after["demoted"]
        res["verified"] = True
        return res

    # -- host-chaos (a whole host fault domain drops mid-repair:
    # recovery-under-host-loss throughput — ISSUE 17, chaos/hosts.py +
    # the host-aware plane) ---------------------------------------------

    def host_chaos(self) -> dict:
        """Recovery throughput while a whole HOST fault domain fails
        mid-run: the same batched fused-repair stream as device-chaos,
        but the plane spans ``--hosts`` simulated fault domains and a
        seeded HostLoss (chaos/hosts.py) takes the last one out at the
        seam's Nth call.  The supervisor must classify ``host_loss``,
        reshrink host-granular (the survivor keeps all its devices),
        run the journal-reclaim hook, complete the batch, and
        re-promote to full host width once the plan clears — zero
        data loss and byte-identical heal are gated in-workload, and
        the row carries the host-granular counter deltas so
        bench_diff's ``host_chaos`` category can never silently
        regress host-loss survival.

        ``--device host`` (the CPU baseline): no plane forms, so the
        process is its one fault domain — losing host 0 demotes
        straight to the ground-truth twin (the width-1 ladder),
        measuring the classification machinery without a device."""
        from ..chaos import BitFlip, ShardErasure, inject
        from ..chaos.hosts import (HostFaultPlan, HostLoss,
                                   arm_host_plan)
        from ..codes.stripe import HashInfo, StripeInfo
        from ..codes.stripe import encode as stripe_encode
        from ..ops.supervisor import global_supervisor
        from ..parallel import plane as planemod
        from ..recovery.orchestrator import healed
        from ..scrub import repair_batched
        a = self.args
        ec = self._instance()
        n = ec.get_chunk_count()
        k = ec.get_data_chunk_count()
        if a.erasures < 1 or a.erasures + a.corruptions >= n:
            raise ValueError("host-chaos needs 1 <= erasures + "
                             "corruptions < n")
        chunk_size = ec.get_chunk_size(a.size)
        width = k * chunk_size
        sinfo = StripeInfo(k, width)
        rng = np.random.default_rng(a.seed)
        objects = []
        for i in range(a.batch):
            obj = rng.integers(0, 256, size=width,
                               dtype=np.uint8).tobytes()
            shards = stripe_encode(sinfo, ec, obj)
            hinfo = HashInfo(n)
            hinfo.append(0, shards)
            objects.append((shards, hinfo))
        hinfos = [h for _, h in objects]
        originals = [s for s, _ in objects]

        prng = np.random.default_rng(a.seed + 1)
        n_pat = max(1, min(4, a.batch))
        pool = []
        for _ in range(n_pat):
            victims = prng.choice(n, size=a.erasures + a.corruptions,
                                  replace=False)
            pool.append(([int(v) for v in victims[:a.erasures]],
                         [int(v) for v in victims[a.erasures:]]))

        def make_stores():
            stores = []
            for i, (shards, _) in enumerate(objects):
                erased, flipped = pool[i % n_pat]
                inj = []
                if erased:
                    inj.append(ShardErasure(shards=list(erased)))
                if flipped:
                    inj.append(BitFlip(shards=list(flipped), flips=1))
                st, _ = inject(shards, inj, seed=a.seed + i,
                               chunk_size=sinfo.chunk_size)
                stores.append(st)
            return stores

        dev = a.device != "host"
        sup = global_supervisor()
        seam = ("engine.fused_repair" if dev else "bench.host_chaos")
        prev_plane = None
        plane = None
        if dev:
            prev_plane = planemod.data_plane()
            plane = planemod.activate(None, hosts=max(2, a.hosts))
        hosts0 = plane.hosts if plane is not None else 1
        # the victim: the LAST host domain (host 0 when no plane can
        # form — the process itself is the one fault domain)
        lost = hosts0 - 1 if hosts0 > 1 else 0
        reclaims: List[str] = []
        prev_reclaim = sup.set_inflight_reclaim(
            lambda s: reclaims.append(s) or 0)

        def fault_script():
            return HostFaultPlan(
                [HostLoss(lost, seam=seam, at=(2 if dev else 1),
                          calls=2)],
                seed=a.seed)

        def run_once():
            stores = make_stores()
            if dev:
                rep = repair_batched(sinfo, ec, stores, hinfos,
                                     device=True)
            else:
                call = (lambda: repair_batched(
                    sinfo, ec, stores, hinfos, device=False))
                rep = sup.dispatch(seam, lambda: call(), (),
                                   host_fn=lambda: call(),
                                   splittable=False)
            if not healed(stores, originals):
                raise RuntimeError("host-chaos: data loss under "
                                   "injected host loss")
            return rep

        try:
            # warm pattern caches + traces with NO faults armed
            run_once()
            before = {key: v for key, v in sup.stats().items()
                      if isinstance(v, int)}
            lat = _LatTimer()
            plans = []
            begin = time.perf_counter()
            for _ in range(a.iterations):
                plan = fault_script()
                prev = arm_host_plan(plan)
                try:
                    lat.run(run_once)
                    plan.clear()
                    # drive the health probe to re-promotion so every
                    # iteration starts at full host width
                    for _ in range(sup.promote_after + 2):
                        sup.tick()
                finally:
                    arm_host_plan(prev)
                plans.append(plan.summary())
            elapsed = time.perf_counter() - begin
            after = sup.stats()
        finally:
            sup.set_inflight_reclaim(prev_reclaim)
            if dev:
                planemod.set_data_plane(prev_plane)
        res = self._result("host-chaos", elapsed,
                           width * a.batch * a.iterations, lat)
        res["erasures"] = a.erasures
        res["hosts"] = hosts0
        res["supervisor"] = {
            key: after[key] - before.get(key, 0)
            for key in ("host_quarantines", "host_repromotions",
                        "journal_redispatches", "retries",
                        "demotions", "quarantines", "repromotions",
                        "host_completions")}
        res["faults_fired"] = sum(p["fired"] for p in plans)
        res["reclaim_calls"] = len(reclaims)
        res["demoted_at_end"] = after["demoted"]
        res["verified"] = True
        return res

    # -- autotune (the roofline-closing config search as a measured
    # workload — ISSUE 14, ceph_tpu/tune/ + tools/autotune.py) ---------

    def autotune_workload(self) -> dict:
        """Profiler-driven config sweep as a bench row
        (metric_version 11): timed min-of-N candidate dispatches with
        byte-identity asserted across every candidate tier
        (``--device jax``), or the host-only analytic roofline sweep
        (``--device host`` — the CPU baseline, zero jax).
        The row carries the before/after utilization rows the tuner
        emitted, the tuned key list, and ``utilization_pct`` (the
        best tuned program's after-utilization — the bench_diff
        ``autotune`` category series, so a tuned config that later
        regresses fails CI)."""
        from ..tune import sweep as tsweep
        a = self.args
        begin = time.perf_counter()
        if a.device == "jax":
            rep = tsweep.timed_sweep(
                plugin=a.plugin, profile=self.profile or None,
                size=a.size, batch=a.batch,
                repeats=max(2, a.iterations), seed=a.seed)
        else:
            rep = tsweep.analytic_sweep(seed=a.seed)
        elapsed = time.perf_counter() - begin
        # bytes actually priced/measured by the sweep (the attribution
        # rows record arg_bytes x observed calls per program)
        total_bytes = sum(
            int(r["arg_bytes"]) * int(r["calls"] or 1)
            for r in rep.attribution if r.get("arg_bytes"))
        res = self._result("autotune", elapsed, max(1, total_bytes))
        res["mode"] = rep.mode
        res["seed"] = rep.seed
        res["tuned_keys"] = sorted(rep.table.entries)
        res["n_tuned"] = len(rep.table)
        res["rows"] = rep.rows
        utils = [r["after"].get("utilization_pct") for r in rep.rows
                 if isinstance(r.get("after"), dict)
                 and isinstance(r["after"].get("utilization_pct"),
                                (int, float))]
        res["utilization_pct"] = max(utils) if utils else None
        head = rep.headline()
        res["improvement_pct"] = (head or {}).get("improvement_pct")
        res["improved_rows"] = len(rep.improved)
        # timed mode asserts byte-identity across every candidate
        # tier in-sweep (a raise aborts the row); analytic mode never
        # dispatches, so there is nothing to diverge
        res["verified"] = True
        return res

    def _run_workload(self) -> dict:
        if self.args.workload == "autotune":
            return self.autotune_workload()
        if self.args.workload == "encode":
            return self.encode()
        if self.args.workload == "degraded":
            return self.degraded()
        if self.args.workload == "repair-batched":
            return self.repair_batched()
        if self.args.workload == "recovery-churn":
            return self.recovery_churn()
        if self.args.workload == "serving":
            return self.serving()
        if self.args.workload == "multichip":
            return self.multichip()
        if self.args.workload == "cluster":
            return self.cluster()
        if self.args.workload == "profile":
            return self.profile_workload()
        if self.args.workload == "scenario":
            return self.scenario_workload()
        if self.args.workload == "tenant-week":
            return self.tenant_week_workload()
        if self.args.workload == "device-chaos":
            return self.device_chaos()
        if self.args.workload == "host-chaos":
            return self.host_chaos()
        return self.decode()


def main(argv: List[str] | None = None) -> int:
    bench = ErasureCodeBench()
    bench.setup(argv if argv is not None else sys.argv[1:])
    res = bench.run()
    if bench.args.json_out:
        print(json.dumps(res))
    else:
        # reference output: "<elapsed seconds>\t<total KiB>"
        print(f"{res['seconds']:.6f}\t{res['total_bytes'] // 1024}")
        if bench.args.verbose:
            print(f"{res['gbps']:.3f} GB/s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    from ..utils.compile_cache import initialize_compile_cache
    initialize_compile_cache()
    raise SystemExit(main())
