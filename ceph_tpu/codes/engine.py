"""Unified device-resident decode/repair engine — the cross-call
composite-matrix pattern cache and the fused decode→re-encode call.

Every plugin's decode is, for a fixed (profile, erasure pattern), ONE
GF(2^8)-linear map — RS/jerasure's inverted Vandermonde submatrix,
shec's minimum-read plan matrix, lrc's probed layer-walk composite,
clay's probed layered composite.  The plugins build those matrices
lazily, but until this module each *instance* rebuilt (and re-traced)
them from scratch: a fresh factory() per scrub pass meant clay re-ran
its impulse probe and jax re-jitted an identical program for every
repair plan.  Two pieces fix that:

- ``PatternCache`` — a process-wide LRU keyed on
  (plugin class, profile, kind, available, erased).  The cached value
  carries the composite matrix AND its hashable static form, so a
  warm hit reuses both the host matrix and the already-traced jit
  program (jit caches key on the static tuple).  A recompile-count
  guard (``builds`` vs ``recompile_budget``) turns unbounded pattern
  churn — the failure mode tpu-lint's static-args rule exists for —
  into an observable counter and, when a budget is armed, a loud
  RuntimeError instead of a silent compile storm.

- ``fused_repair_call`` — one jitted program per (plugin, pattern)
  that decodes the erased shards AND re-encodes the full parity set
  from the survivors in a single device dispatch: the batched scrub
  repair path (scrub/deep_scrub.py::repair_batched) crosses
  host↔device once per erasure-pattern batch instead of once per
  stripe.  Byte-identical to the per-stripe path by construction (it
  composes the same decode_chunks_jax / encode_chunks_jax the
  per-stripe path uses).

- ``serve_decode_generic`` — for a plain w=8 matrix code the linear
  map is a small (erased, k) block, so the serve decode takes it as an
  operand (``decode_table``, the blocks cached here per pattern): one
  program per (plugin, profile, erasure count) serves every pattern,
  and stripes of different patterns share a dispatch.

Engine selection for the matrix applies themselves lives in
ops/pallas_gf.py::select_matrix_engine (the Pallas→XLA→numpy table,
documented in docs/PERF.md); this module is the layer above it.

Every eager dispatch through the cached programs routes through the
supervised dispatch plane (ops/supervisor.py): transient errors
retry, RESOURCE_EXHAUSTED splits the batch rung, persistent backend
loss demotes the fallback tier live (the numpy ground-truth twin
completes the dispatch byte-identically), and mesh-member failure
quarantines a device and rebuilds the sharded program on the shrunk
plane.  Traced calls bypass supervision entirely, so jitted programs
stay supervision-free by construction (the audit entries pin it).
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Optional, Tuple

from ..telemetry import metrics as tel
from ..telemetry import tracing as trc
from ..utils.log import dout
from ..utils.locks import make_lock

DEFAULT_MAX_PATTERNS = 512


class PatternCache:
    """Cross-call LRU of per-(plugin, profile, erasure-pattern)
    decode artifacts, with a recompile-count guard.

    Values are opaque to the cache (matrix/static tuples, jitted
    callables); the contract is only that a given key always maps to
    the same value, so eviction + rebuild is correct at any size."""

    def __init__(self, max_patterns: int = DEFAULT_MAX_PATTERNS,
                 recompile_budget: Optional[int] = None) -> None:
        self.max_patterns = max_patterns
        # builds above this raise (tests arm it to pin "bounded jit
        # recompile count"); None = log-once observability only
        self.recompile_budget = recompile_budget
        self._lock = make_lock("codes.engine.PatternCache._lock")
        self._entries: "OrderedDict[tuple, object]" = OrderedDict()
        self.hits = 0
        self.builds = 0
        self.evictions = 0
        self._warned = False

    def get_or_build(self, key: tuple, builder: Callable[[], object]):
        hit = self.get(key)
        if hit is not None:
            return hit
        # build OUTSIDE the lock: clay's impulse probe can take
        # seconds and must not serialize unrelated patterns
        with tel.record_dispatch("pattern_cache_build"):
            value = builder()
        with self._lock:
            race = self._entries.get(key)
            if race is not None:
                self.hits += 1
                tel.counter("pattern_cache_hits")
                return race
            self.builds += 1
            tel.counter("pattern_cache_builds")
            if (self.recompile_budget is not None
                    and self.builds > self.recompile_budget):
                tel.counter("pattern_cache_budget_exceeded")
                tel.event("pattern_cache_budget_exceeded",
                          builds=self.builds,
                          budget=self.recompile_budget)
                # an armed budget tripping IS a production incident
                # (pattern churn = a compile storm): freeze the
                # flight-recorder post-mortem before raising
                from ..telemetry import recorder
                recorder.trip(
                    "recompile_budget",
                    f"{self.builds} builds > budget "
                    f"{self.recompile_budget}",
                    builds=self.builds, budget=self.recompile_budget,
                    key=str(key))
                raise RuntimeError(
                    f"pattern-cache recompile budget exceeded: "
                    f"{self.builds} composite builds > "
                    f"{self.recompile_budget} (unbounded erasure-pattern "
                    f"churn would jit-compile per call)")
            self._entries[key] = value
            while len(self._entries) > self.max_patterns:
                self._entries.popitem(last=False)
                self.evictions += 1
                tel.counter("pattern_cache_evictions")
                if not self._warned:
                    self._warned = True
                    dout("ec", 1,
                         f"pattern cache exceeded {self.max_patterns} "
                         f"patterns; evicting LRU (repeat plans will "
                         f"re-trace)")
            return value

    def get(self, key: tuple):
        """The value cached under ``key`` (counted as a hit and made
        the most recent), or None."""
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                tel.counter("pattern_cache_hits")
            return hit

    def stats(self) -> dict:
        with self._lock:
            return {"patterns": len(self._entries), "hits": self.hits,
                    "builds": self.builds, "evictions": self.evictions}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = 0
            self.builds = 0
            self.evictions = 0
            self._warned = False


_global: Optional[PatternCache] = None
_global_lock = make_lock("codes.engine._global_lock")


def global_pattern_cache() -> PatternCache:
    global _global
    with _global_lock:
        if _global is None:
            _global = PatternCache()
        return _global


def set_global_pattern_cache(cache: Optional[PatternCache]
                             ) -> Optional[PatternCache]:
    """Swap the process cache (tests); returns the previous one."""
    global _global
    with _global_lock:
        prev = _global
        _global = cache
        return prev


def pattern_key(ec, kind: str, available: tuple, erased: tuple,
                extra: tuple = ()) -> tuple:
    """Cache key for one plugin instance's (pattern, artifact kind).

    Profile-derived, not instance-derived: two factory() calls with
    the same profile share every composite matrix and jit trace."""
    return (type(ec).__name__,
            tuple(sorted((str(k), str(v))
                         for k, v in ec.get_profile().items())),
            kind, tuple(available), tuple(erased)) + tuple(extra)


# -- fused decode → re-encode (the batched scrub repair device call) ----

def _resolve_mesh(mesh):
    from ..parallel.plane import resolve_plane
    plane = resolve_plane(mesh)
    if plane is not None and plane.n_devices < 2:
        return None
    return plane


def _profiler():
    from ..telemetry.profiler import global_profiler
    return global_profiler()


def _shard_program(raw, plane, n_out: int, n_in: int = 1):
    """Wrap a per-shard body of ``n_in`` rank-3 operands, each
    (B_local, ..., ...), -> rank-3 outputs in shard_map over the
    plane's stripe axis: every operand's batch sharded, every
    trace-time constant (decode/encode matrices, GF tables) replicated
    by construction, non-dividing batches zero-padded and the pad rows
    sliced off the outputs.  The body traces under
    ``plane.single_device()`` so its engine selection picks the
    single-device tier (no nested meshes).  ONE jitted program = ONE
    device dispatch per call."""
    import jax
    import jax.numpy as jnp

    from ..parallel.plane import single_device
    from ..utils.shard import batch_spec, shard_map_compat

    ndev = plane.n_devices
    spec = batch_spec(plane.axis, 3)

    def body(*local):
        with single_device():
            return raw(*local)

    sharded = shard_map_compat(
        body, plane.mesh,
        in_specs=tuple([spec] * n_in) if n_in > 1 else spec,
        out_specs=tuple([spec] * n_out) if n_out > 1 else spec)

    @jax.jit
    def fn(*operands):
        b = operands[0].shape[0]
        pad = (-b) % ndev
        if pad:
            operands = tuple(
                jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
                for x in operands)
        out = sharded(*operands)
        if not pad:
            return out
        if n_out == 1:
            return out[:b]
        return tuple(o[:b] for o in out)

    return fn


def fused_repair_call(ec, available: Tuple[int, ...],
                      erased: Tuple[int, ...], mesh=None):
    """One jitted fn: survivors (B, n_avail, C) uint8 →
    (rec (B, n_erased, C), parity (B, m, C)) in a SINGLE device
    dispatch — decode of every erased shard plus the full parity
    re-encode the repair gate needs, fused so batched repair is one
    host↔device round-trip per erasure-pattern batch.

    Shard space follows the plugin's decode surface (identity chunk
    ids, or lrc's global positions via get_chunk_mapping); data chunks
    for the re-encode are assembled from survivor and decoded columns
    by static index, so the whole body jit-fuses.  Cached per
    (plugin, profile, pattern) in the global PatternCache — repeat
    repair plans hit the warm trace.  (Repair stays per pattern on
    the serve path too; only the serve decode of a plain w=8 matrix
    code is pattern-generic, :func:`serve_decode_generic`.)

    When a data plane is active (parallel/plane.py; ``mesh`` overrides
    it — a DataPlane, or falsy to force single-device), the program is
    the SHARDED variant: the same decode→re-encode body under
    shard_map with the stripe batch sharded over the mesh and the
    matrices replicated — still exactly one device dispatch per
    pattern batch, byte-identical, cached in the same PatternCache
    keyspace under a mesh-suffixed key."""
    import jax
    import jax.numpy as jnp

    from .stripe import _chunk_mapping

    available = tuple(available)
    erased = tuple(erased)
    plane = _resolve_mesh(mesh)
    extra = ("mesh", plane.n_devices) if plane is not None else ()
    key = pattern_key(ec, "fused-repair", available, erased, extra)

    def build():
        mapping = _chunk_mapping(ec)
        k = ec.get_data_chunk_count()
        aidx = {s: t for t, s in enumerate(available)}
        eidx = {s: t for t, s in enumerate(erased)}
        src = []
        for c in range(k):
            shard = mapping[c]
            if shard in aidx:
                src.append(("avail", aidx[shard]))
            elif shard in eidx:
                src.append(("rec", eidx[shard]))
            else:
                raise IOError(
                    f"data shard {shard} neither available nor erased "
                    f"in pattern (avail={available}, erased={erased})")

        def raw(stack):
            # named_scope is pure trace metadata (no primitives — the
            # jaxpr audit stays byte-identical); it labels the decode
            # and re-encode regions in TensorBoard device traces so
            # they line up with the host "dispatch" span around the
            # call
            with jax.named_scope("fused_repair.decode"):
                rec = ec.decode_chunks_jax(stack, available, erased)
            cols = [stack[:, t, :] if where == "avail" else rec[:, t, :]
                    for where, t in src]
            data = jnp.stack(cols, axis=1)
            with jax.named_scope("fused_repair.reencode"):
                parity = ec.encode_chunks_jax(data)
            return rec, parity

        fn = (jax.jit(raw) if plane is None
              else _shard_program(raw, plane, n_out=2))

        # the supervised-dispatch couplings (ops/supervisor.py): the
        # numpy ground-truth twin (byte-identical by construction —
        # serve/batcher.py::_host_repair mirrors this exact column
        # assembly) and the rebuild hook that re-derives the RAW
        # program after a live tier demotion / plane reshrink (the
        # pattern cache was cleared, so the rebuilt program lands on
        # the demoted tier or the shrunk plane)
        def host_twin(stack):
            import numpy as np

            from ..serve.batcher import _host_repair
            return _host_repair(ec, np.asarray(stack), available,
                                erased)

        def rebuild():
            return fused_repair_call(ec, available, erased,
                                     mesh=mesh)._raw

        ndev = plane.n_devices if plane is not None else 1
        # the PatternCache key IS the program identity (class +
        # profile + kind + pattern + mesh) — reuse it so two profiles
        # of one plugin class can never share an attribution row.
        # config records whether this program was BUILT under a tuned
        # best-config table (ISSUE 14: consultation happens at build
        # time, inside this cached builder, so tuned configs ride the
        # warm path with zero recompiles; installing a table clears
        # this cache, so the label can never go stale)
        from ..tune.table import active_source
        prof_key = ("prof",) + key
        prof_labels = dict(
            plugin=type(ec).__name__, kind="fused-repair",
            profile=",".join(f"{pk}={pv}" for pk, pv in
                             sorted(ec.get_profile().items())),
            pattern="e" + "_".join(map(str, erased)),
            engine="mesh" if plane is not None else "device",
            devices=ndev, config=active_source()[0])

        def timed(stack):
            # host-side dispatch latency histogram.  Tracer inputs
            # mean WE are being traced into a larger program — record
            # nothing (a trace-time clock read is fiction) and leave
            # the jaxpr telemetry-free by construction.
            eager = not isinstance(stack, jax.core.Tracer)
            prof = _profiler()
            if eager and tel.enabled():
                if plane is not None:
                    tel.counter("engine_mesh_dispatches",
                                tier="fused-repair",
                                devices=str(plane.n_devices))
                # cost-attribution capture (telemetry/profiler.py):
                # first eager dispatch lowers the program once for
                # XLA cost_analysis — zero backend compiles, so the
                # warm==0 sentinel cannot see it
                # keyed per batch rung: one jit wrapper serves many
                # stripe-batch shapes, each its own compiled program
                pk = prof_key + (int(stack.shape[0]),)
                prof.capture(pk, fn, (stack,),
                             name="engine.fused_repair",
                             batch=int(stack.shape[0]), **prof_labels)
            else:
                pk = prof_key
            if eager and trc.enabled():
                # causal-trace link (ISSUE 15): name the EXACT
                # profiler series this dispatch rides, so a trace's
                # program event joins attribution_rows() per-trace
                trc.note_program(
                    "engine.fused_repair",
                    dict(prof_labels, batch=int(stack.shape[0])))
            with tel.record_dispatch(
                    "engine_fused_repair_dispatch",
                    eager=eager, plugin=type(ec).__name__), \
                    prof.timed(pk, eager=eager):
                if not eager:
                    return fn(stack)
                from ..ops.supervisor import global_supervisor
                return global_supervisor().dispatch(
                    "engine.fused_repair", fn, (stack,),
                    host_fn=host_twin, rebuild=rebuild)

        timed._raw = fn
        return timed

    return global_pattern_cache().get_or_build(key, build)


# -- serving dispatch seam (serve/batcher.py's one device call) ---------

def _serve_timed(ec, fn, key: tuple, kind: str, pattern: str, seam: str,
                 op: str, plane, host_twin, rebuild):
    """The eager face of one serve program ``fn(stack, *operands)``:
    cost-attribution capture, the causal-trace program link and the
    ``serve_dispatch`` histogram around a supervised dispatch (tracer
    inputs record nothing and call ``fn`` straight, so jaxprs stay
    telemetry-free).  ``key`` is the PatternCache key — the program
    identity, so two profiles of one plugin class never share an
    attribution row; ``config`` = tuned|default records which config
    regime BUILT the program (see fused_repair_call)."""
    import jax

    from ..tune.table import active_source
    ndev = plane.n_devices if plane is not None else 1
    prof_key = ("prof",) + key
    prof_labels = dict(
        plugin=type(ec).__name__, kind=kind,
        profile=",".join(f"{pk}={pv}" for pk, pv in
                         sorted(ec.get_profile().items())),
        pattern=pattern,
        engine="mesh" if plane is not None else "device",
        devices=ndev, config=active_source()[0])

    def timed(*args):
        eager = not any(isinstance(a, jax.core.Tracer) for a in args)
        batch = int(args[0].shape[0])
        prof = _profiler()
        if eager and tel.enabled():
            if plane is not None:
                tel.counter("engine_mesh_dispatches", tier=kind,
                            devices=str(plane.n_devices))
            # keyed per batch rung: one jit wrapper serves many
            # stripe-batch shapes, each its own compiled program
            pk = prof_key + (batch,)
            prof.capture(pk, fn, args, name="engine.serve_dispatch",
                         batch=batch, **prof_labels)
        else:
            pk = prof_key
        if eager and trc.enabled():
            # causal-trace link: see fused_repair_call
            trc.note_program("engine.serve_dispatch",
                             dict(prof_labels, batch=batch))
        with tel.record_dispatch(
                "serve_dispatch", eager=eager,
                op=op, plugin=type(ec).__name__), \
                prof.timed(pk, eager=eager):
            if not eager:
                return fn(*args)
            from ..ops.supervisor import global_supervisor
            return global_supervisor().dispatch(
                seam, fn, args, host_fn=host_twin, rebuild=rebuild)

    timed._raw = fn
    return timed


def serve_dispatch_call(ec, op: str, available: Tuple[int, ...] = (),
                        erased: Tuple[int, ...] = (), mesh=None):
    """One cached, jitted program per (plugin, profile, op, erasure
    pattern): the seam the continuous batcher (serve/batcher.py) fires
    its per-pattern shape buckets through.  (The batcher sends the
    decode of a plain w=8 matrix code through
    :func:`serve_decode_generic` instead, one program for every
    pattern; this seam still serves every op of every code.)

    The cache key is :func:`pattern_key` with ``kind=f"serve-{op}"`` —
    the SAME keying the decode-matrix and fused-repair artifacts use,
    so a serving bucket and a scrub repair plan for the same pattern
    share the composite matrices underneath, and steady-state traffic
    over a warmed bucket ladder compiles NOTHING (the armed recompile
    budget turns violations into a loud RuntimeError; the tpu-audit
    sentinel on ``serve.dispatch`` pins warm == 0 compiles forever).

    - ``encode``: stack ``(B, k, C)`` uint8 → parity ``(B, m, C)``
    - ``decode``: stack ``(B, n_avail, C)`` survivors → ``(B, n_erased,
      C)`` reconstructed chunks
    - ``repair``: delegates to :func:`fused_repair_call` — the batcher
      reuses the scrub path's decode→re-encode program (and its cache
      entry) verbatim.

    With an active data plane (or an explicit ``mesh``), the program
    is the sharded variant — the same body under shard_map, stripe
    batch sharded, one dispatch per bucket fire, byte-identical —
    cached under a mesh-suffixed key in the same keyspace, so serving
    transparently fans out across devices."""
    if op == "repair":
        return fused_repair_call(ec, available, erased, mesh=mesh)
    if op not in ("encode", "decode"):
        raise ValueError(f"serve op {op!r} must be encode|decode|repair")
    import jax

    available = tuple(available)
    erased = tuple(erased)
    plane = _resolve_mesh(mesh)
    extra = ("mesh", plane.n_devices) if plane is not None else ()
    key = pattern_key(ec, f"serve-{op}", available, erased, extra)

    def build():
        if op == "encode":
            def raw(stack):
                return ec.encode_chunks_jax(stack)
        else:
            def raw(stack):
                return ec.decode_chunks_jax(stack, available, erased)

        fn = (jax.jit(raw) if plane is None
              else _shard_program(raw, plane, n_out=1))

        # supervised-dispatch couplings: the numpy batch surfaces are
        # the ground-truth twin (the serve host executor runs them —
        # byte-identical pinned in tests/test_serve.py); rebuild
        # re-derives the raw program post-demotion/reshrink
        def host_twin(stack):
            import numpy as np
            s = np.asarray(stack)
            if op == "encode":
                return np.asarray(ec.encode_chunks_batch(s))
            return np.asarray(ec.decode_chunks_batch(
                s, available, erased))

        def rebuild():
            return serve_dispatch_call(ec, op, available, erased,
                                       mesh=mesh)._raw

        return _serve_timed(ec, fn, key, f"serve-{op}",
                            "e" + "_".join(map(str, erased)),
                            f"engine.serve-{op}", op, plane, host_twin,
                            rebuild)

    return global_pattern_cache().get_or_build(key, build)


# -- pattern-generic decode of matrix codes (serve/batcher.py) ----------

def pattern_generic_decode(ec) -> bool:
    """Whether ``ec`` decodes by the plain w=8 matrix-code decode
    (jerasure reed_sol_van / reed_sol_r6_op, isa): one decode-rows
    block per erasure pattern, so the serve decode can take the rows
    as an operand.  Codes that override the decode (shec's plans) and
    the composite codes (clay, lrc) are not."""
    from .techniques import MatrixCodeMixin
    cls = type(ec)
    return (isinstance(ec, MatrixCodeMixin)
            and getattr(ec, "w", None) == 8
            and cls.decode_chunks_jax is MatrixCodeMixin.decode_chunks_jax
            and cls.decode_chunks_batch
            is MatrixCodeMixin.decode_chunks_batch)


def generic_decode_key(ec, n_erased: int, extra: tuple = ()) -> tuple:
    """PatternCache key of the pattern-generic decode program of
    (plugin, profile, erasure count)."""
    return pattern_key(ec, "serve-decode-generic", (), (),
                       ("erased", int(n_erased)) + tuple(extra))


def decode_table(ec, patterns, index, rung: int):
    """The ``(rung, e, k)`` uint8 coefficient operand of one fire of
    the pattern-generic decode: row ``i`` is the decode rows
    (``ec.decode_rows``) of ``patterns[index[i]]``, each pattern an
    ``(available[:k], erased)`` pair; rows whose index is negative or
    past ``len(index)`` (pad stripes, dead pages) are zero.  Each
    pattern's rows come from the bounded process PatternCache,
    inverted on a miss (``engine_decode_matrix{outcome=hit|miss}``,
    one per pattern); span ``engine.decode_table``."""
    import numpy as np

    from ..telemetry import span

    with span("engine.decode_table",
              metric="engine_decode_table_seconds"):
        cache = global_pattern_cache()
        rows = []
        for available, erased in patterns:
            key = pattern_key(ec, "decode-rows", available, erased)
            hit = cache.get(key)
            tel.counter("engine_decode_matrix",
                        outcome="miss" if hit is None else "hit")
            if hit is None:
                hit = cache.get_or_build(
                    key, lambda a=available, e=erased: ec.decode_rows(a, e))
            rows.append(hit)
        e = len(patterns[0][1]) if patterns else 0
        out = np.zeros((rung, e, ec.get_data_chunk_count()), np.uint8)
        index = np.asarray(index, np.int64)
        live = index >= 0
        if live.any():
            out[:len(index)][live] = np.stack(rows)[index[live]]
        return out


def serve_decode_generic(ec, n_erased: int, mesh=None):
    """One cached, jitted decode program per (plugin, profile, erasure
    count) of a :func:`pattern_generic_decode` code: ``(stack (B, k,
    C), coef (B, n_erased, k))`` → ``(B, n_erased, C)``, the decode
    rows of every stripe an operand (:func:`decode_table`), so the
    stripes of all erasure patterns with ``n_erased`` losses share the
    program and a dispatch.  jit compiles it once per (rung, chunk
    size), as the per-pattern programs of :func:`serve_dispatch_call`.

    With an active data plane the stack and the coefficients are
    sharded alike over the stripe axis, under a mesh-suffixed key."""
    import jax

    plane = _resolve_mesh(mesh)
    extra = ("mesh", plane.n_devices) if plane is not None else ()
    key = generic_decode_key(ec, n_erased, extra)

    def build():
        def raw_operand(stack, coef):
            return ec.decode_chunks_operand_jax(stack, coef)

        fn = (jax.jit(raw_operand) if plane is None
              else _shard_program(raw_operand, plane, n_out=1, n_in=2))

        def host_twin(stack, coef):
            import numpy as np
            return ec.decode_chunks_operand_batch(np.asarray(stack),
                                                  np.asarray(coef))

        def rebuild():
            return serve_decode_generic(ec, n_erased, mesh=mesh)._raw

        return _serve_timed(ec, fn, key, "serve-decode-generic",
                            f"n{int(n_erased)}", "engine.serve-decode",
                            "decode", plane, host_twin, rebuild)

    return global_pattern_cache().get_or_build(key, build)


# -- ragged paged serving dispatch (ISSUE 18) ---------------------------

def _shard_program_ragged(raw, plane, n_out: int):
    """Mesh variant of a ragged (pool, mask) body: the PAGE axis is
    the sharded axis (pages are independent mini-chunks, so they fan
    out like stripes), the mask sharded alongside, matrices
    replicated.  Non-dividing pools zero-pad pages with a ZERO mask —
    dead by construction, so the pad computes zeros and is sliced
    off."""
    import jax
    import jax.numpy as jnp

    from ..parallel.plane import single_device
    from ..utils.shard import batch_spec, shard_map_compat

    ndev = plane.n_devices
    spec3 = batch_spec(plane.axis, 3)
    spec1 = batch_spec(plane.axis, 1)

    def body(local_pool, local_mask):
        with single_device():
            return raw(local_pool, local_mask)

    sharded = shard_map_compat(
        body, plane.mesh, in_specs=(spec3, spec1),
        out_specs=tuple([spec3] * n_out) if n_out > 1 else spec3)

    @jax.jit
    def fn(pool, mask):
        p = pool.shape[0]
        pad = (-p) % ndev
        if pad:
            pool = jnp.pad(pool, ((0, pad), (0, 0), (0, 0)))
            mask = jnp.pad(mask, ((0, pad),))
        out = sharded(pool, mask)
        if not pad:
            return out
        if n_out == 1:
            return out[:p]
        return tuple(o[:p] for o in out)

    return fn


def _ragged_surface(ec, op: str):
    """The plugin's true ragged surface when it has one (matrix /
    bitmatrix / clay composite families), else None — the generic
    mask-gate body runs instead, byte-identically."""
    return getattr(type(ec), f"{op}_chunks_ragged_jax", None)


def serve_dispatch_ragged(ec, op: str, available: Tuple[int, ...] = (),
                          erased: Tuple[int, ...] = (), *,
                          pages: int, page_size: int, mesh=None):
    """ONE cached, jitted ragged program per (plugin, profile, op,
    erasure pattern, pool geometry): the paged batcher's device seam
    (serve/pool.py stages the pool; serve/batcher.py fires it here).

    The program signature is ``(pool, mask)`` — pool
    ``(pages, rows, page_size)`` uint8, mask ``(pages,)`` {0,1} — and
    the mask is a TRACED operand: every occupancy of the pool runs
    the SAME compiled program, so the cached-program count for a
    serving day is |patterns|, not |buckets| x |ladder| (the dense
    ladder's per-rung programs).  Dead pages compute zeros in every
    tier (GF linearity), so demux never reads them.

    - ``encode``: pool pages are (k, page_size) mini-chunks -> parity
      pages (pages, m, page_size)
    - ``decode``: survivor pages -> (pages, n_erased, page_size)
    - ``repair``: the fused decode -> column-assembly -> re-encode of
      fused_repair_call, on the masked page batch -> (rec, parity)

    On TPU backends the pool operand is DONATED: steady-state serving
    re-uses the previous fire's HBM pages instead of allocating per
    dispatch (CPU/GPU skip donation — XLA:CPU would warn and copy).
    With an active data plane the program shards the PAGE axis
    (pages are independent mini-chunks) under a mesh-suffixed key in
    the same PatternCache keyspace."""
    import jax

    if op not in ("encode", "decode", "repair"):
        raise ValueError(f"serve op {op!r} must be encode|decode|repair")
    available = tuple(available)
    erased = tuple(erased)
    plane = _resolve_mesh(mesh)
    extra = ("paged", int(pages), int(page_size))
    if plane is not None:
        extra += ("mesh", plane.n_devices)
    key = pattern_key(ec, f"serve-{op}-ragged", available, erased,
                      extra)

    def build():
        import jax.numpy as jnp

        from ..ops.pallas_gf import mask_pages

        if op == "repair":
            from .stripe import _chunk_mapping
            mapping = _chunk_mapping(ec)
            k = ec.get_data_chunk_count()
            aidx = {s: t for t, s in enumerate(available)}
            eidx = {s: t for t, s in enumerate(erased)}
            src = []
            for c in range(k):
                shard = mapping[c]
                if shard in aidx:
                    src.append(("avail", aidx[shard]))
                elif shard in eidx:
                    src.append(("rec", eidx[shard]))
                else:
                    raise IOError(
                        f"data shard {shard} neither available nor "
                        f"erased in pattern (avail={available}, "
                        f"erased={erased})")

        dec = _ragged_surface(ec, "decode")
        enc = _ragged_surface(ec, "encode")

        def raw(pool, mask):
            if op == "encode":
                if enc is not None:
                    return enc(ec, pool, mask)
                return ec.encode_chunks_jax(mask_pages(pool, mask))
            if op == "decode":
                if dec is not None:
                    return dec(ec, pool, mask, available, erased)
                return ec.decode_chunks_jax(mask_pages(pool, mask),
                                            available, erased)
            # repair: the fused_repair_call body on the page batch —
            # survivors mask-gated ONCE so the column assembly and
            # the re-encode see zeros on dead pages
            x = mask_pages(pool, mask)
            with jax.named_scope("serve_ragged.decode"):
                if dec is not None:
                    rec = dec(ec, pool, mask, available, erased)
                else:
                    rec = ec.decode_chunks_jax(x, available, erased)
            cols = [x[:, t, :] if where == "avail" else rec[:, t, :]
                    for where, t in src]
            data = jnp.stack(cols, axis=1)
            with jax.named_scope("serve_ragged.reencode"):
                parity = ec.encode_chunks_jax(data)
            return rec, parity

        n_out = 2 if op == "repair" else 1
        if plane is not None:
            fn = _shard_program_ragged(raw, plane, n_out=n_out)
        elif jax.default_backend() == "tpu":
            # donate the pool's HBM buffer forward (see docstring);
            # the mask is tiny and NOT donated (the batcher re-reads
            # it for demux bookkeeping)
            fn = jax.jit(raw, donate_argnums=(0,))
        else:
            fn = jax.jit(raw)

        # supervised-dispatch couplings (ops/supervisor.py): numpy
        # ground truth = zero the dead pages, then the same batch
        # surfaces the dense host twin runs (byte-identical pinned in
        # tests/test_serve.py); rebuild re-derives the program after
        # a tier demotion / plane reshrink
        def host_twin(pool, mask):
            import numpy as np
            x = np.asarray(pool) * (np.asarray(mask) != 0).astype(
                np.uint8)[:, None, None]
            if op == "encode":
                return np.asarray(ec.encode_chunks_batch(x))
            if op == "decode":
                return np.asarray(ec.decode_chunks_batch(
                    x, available, erased))
            from ..serve.batcher import _host_repair
            return _host_repair(ec, x, available, erased)

        def rebuild():
            return serve_dispatch_ragged(
                ec, op, available, erased, pages=pages,
                page_size=page_size, mesh=mesh)._raw

        ndev = plane.n_devices if plane is not None else 1
        from ..tune.table import active_source
        prof_key = ("prof",) + key
        prof_labels = dict(
            plugin=type(ec).__name__, kind=f"serve-{op}-ragged",
            profile=",".join(f"{pk}={pv}" for pk, pv in
                             sorted(ec.get_profile().items())),
            pattern="e" + "_".join(map(str, erased)),
            engine="mesh" if plane is not None else "device",
            devices=ndev, config=active_source()[0])

        def timed(pool, mask):
            # same trace-eagerness discipline as serve_dispatch_call
            eager = not (isinstance(pool, jax.core.Tracer)
                         or isinstance(mask, jax.core.Tracer))
            prof = _profiler()
            if eager and tel.enabled():
                if plane is not None:
                    tel.counter("engine_mesh_dispatches",
                                tier=f"serve-{op}-ragged",
                                devices=str(plane.n_devices))
                # ONE program per pattern: the profiler key carries
                # the (static) pool page count, not a rung
                pk = prof_key + (int(pool.shape[0]),)
                prof.capture(pk, fn, (pool, mask),
                             name="engine.serve_dispatch_ragged",
                             batch=int(pool.shape[0]), **prof_labels)
            else:
                pk = prof_key
            if eager and trc.enabled():
                trc.note_program(
                    "engine.serve_dispatch_ragged",
                    dict(prof_labels, batch=int(pool.shape[0])))
            with tel.record_dispatch(
                    "serve_dispatch_ragged", eager=eager,
                    op=op, plugin=type(ec).__name__), \
                    prof.timed(pk, eager=eager):
                if not eager:
                    return fn(pool, mask)
                from ..ops.supervisor import global_supervisor
                return global_supervisor().dispatch(
                    f"engine.serve-{op}-ragged", fn, (pool, mask),
                    host_fn=host_twin, rebuild=rebuild)

        timed._raw = fn
        return timed

    return global_pattern_cache().get_or_build(key, build)
