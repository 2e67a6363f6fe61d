"""Continuous shape-bucketed batcher — ragged request streams onto
fixed-shape device programs, with zero warm recompiles.

The data plane underneath (codes/engine.py + ops/) runs ONE jitted
program per (plugin, profile, op, erasure pattern, array shape).  A
ragged serving stream therefore has exactly one efficient mapping onto
it, the Ragged-Paged-Attention discipline (PAPERS.md, arxiv
2604.15464) translated to erasure coding:

- **Shape buckets.**  Requests coalesce into buckets keyed EXACTLY
  like the PatternCache — (plugin class, profile, serve-op kind,
  available, erased) via :func:`~ceph_tpu.codes.engine.pattern_key`,
  extended with the chunk size — so bucket identity ≡ device-program
  identity and a warm bucket can never trace a new program.
- **Pattern-generic decode.**  The decode of a plain w=8 matrix code
  (jerasure reed_sol_van / reed_sol_r6_op, isa;
  :func:`~ceph_tpu.codes.engine.pattern_generic_decode`) is one
  bucket per (plugin, profile, erasure count, chunk size) whatever the
  erasure pattern: each request's first k survivor rows are packed,
  and its pattern's decode rows ride the stack as a ``(rung, e, k)``
  coefficient operand (:func:`~ceph_tpu.codes.engine.decode_table`)
  into :func:`~ceph_tpu.codes.engine.serve_decode_generic` — one
  program per rung for every pattern.  ``serve_dispatch_patterns``
  observes the distinct patterns of every decode/repair fire.
- **The rung ladder.**  The batch dimension is padded up to a small
  fixed ladder (default 1/4/16/64) instead of dispatching every
  occupancy as its own shape: |ladder| programs per bucket, warmed
  once, reused forever.  Padding waste is counted per dispatch
  (``padding_stats()``) — the SLO report carries the overhead ratio,
  because padding is the price of shape stability and must stay
  visible.
- **Deadline-aware firing.**  A bucket fires when it reaches the top
  rung (full) OR when its oldest request's slack — deadline minus now
  minus the bucket's EWMA service estimate — runs out.  Under load
  batches fill; under trickle traffic nobody waits past their
  deadline for co-batchees that never come.

Execution goes through :func:`~ceph_tpu.codes.engine.serve_dispatch_call`
(``executor="device"``; repair reuses the scrub path's fused
decode→re-encode program and cache entry) or the plugins' numpy batch
surfaces (``executor="host"`` — byte-identical by the cross-pinning in
tests/, and the zero-compile tier the ``serve.batcher`` host audit
entry runs).  Every dispatch is demuxed back to per-request
:class:`~ceph_tpu.serve.queue.EcResult`\\ s; padded rows are dropped on
the host side, so batched results are byte-identical to per-request
execution by construction (pinned for all five plugin families in
tests/test_serve.py).

**Spans** (telemetry/spans.py; each feeds a self-time histogram):
``serve.admit`` (classifying drained requests; a full-bucket fire
nested in it is its child), and under each ``serve.batch``:
``serve.pack`` (copying the payloads into the staging buffer),
``serve.dispatch`` (the engine call: H2D and launch), ``serve.wait``
(the device result), ``serve.d2h`` (its copy to the host) and
``serve.demux`` (slicing out the per-request results).
``serve_dispatches`` carries the fire ``reason``: ``full``,
``deadline`` or ``flush``.

**Staging.**  Dense fires pack into one reused host buffer per
``(rows, chunk_size)``, sized for the top rung and allocated at warm-up
(``_Staging``): a rung-``r`` fire launches its leading ``r`` rows, so
the program sees the same shape and bytes as a fresh zero-padded stack
while no fire allocates (or page-faults) a stack of its own.
``serve_pack_buffers`` counts each acquisition, ``outcome`` =
``allocated`` or ``reused``.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..telemetry import metrics as tel
from ..telemetry import span
from ..telemetry import tracing
from ..utils.detcheck import default_clock
from ..utils.log import dout
from .queue import AdmissionQueue, EcRequest, EcResult

# padded stripe-batch sizes: every dispatch shape's batch dim is one
# of these, so steady-state traffic holds |ladder| programs per bucket
LADDER = (1, 4, 16, 64)


def tuned_ladder(default: Tuple[int, ...] = LADDER) -> Tuple[int, ...]:
    """The autotuner's rung-ladder consultation seam (ISSUE 14): the
    tuned ladder from the installed best-config table (kind
    ``serve-ladder``), validated strictly-increasing positive ints,
    else ``default`` byte-identically.  Consulted at batcher BUILD
    time only — a running batcher's ladder (and its warmed program
    set) never changes underneath it."""
    from ..tune.table import consult
    cfg = consult("serve-ladder")
    if cfg:
        lad = cfg.get("ladder")
        try:
            t = tuple(int(x) for x in lad)
        except (TypeError, ValueError):
            return tuple(default)
        if t and all(x > 0 for x in t) and t == tuple(sorted(set(t))):
            return t
    return tuple(default)

# EWMA smoothing for the per-bucket service-time estimate
_EWMA_ALPHA = 0.3

# fault-injection seam for tools/replay_bisect.py: when set, every
# measured service time passes through this hook BEFORE the EWMA
# update, so the perturbation propagates into slack deadlines and
# changes downstream batch composition — exactly the kind of quiet
# nondeterminism the bisector exists to localize.  Signature:
# (service_s, dispatch_index) -> service_s.  Never set in production;
# the replay_bisect self-test installs a deterministic jitter on run
# B only and pins the first divergent checkpoint.
_SERVICE_JITTER: Optional[Callable[[float, int], float]] = None


def set_service_jitter(
        fn: Optional[Callable[[float, int], float]]) -> None:
    """Install (or clear, with ``None``) the service-time jitter
    hook.  Test/bisect seam — see ``_SERVICE_JITTER`` above."""
    global _SERVICE_JITTER
    _SERVICE_JITTER = fn

# floor on the service estimate (seconds): a fresh bucket with no
# dispatch history must still fire BEFORE its deadline by enough to
# land the dispatch — with a zero estimate it would fire exactly at
# the deadline and the service time would push every completion past
# it (found by the first FakeClock scenario run)
_MIN_SLACK = 1e-3


def rung_for(n: int, ladder: Tuple[int, ...],
             strict: bool = False) -> int:
    """Smallest ladder rung holding ``n`` requests.  Occupancy above
    the top rung maps to the TOP rung — the batcher splits oversized
    admissions into top-rung batches instead of erroring (each slice
    its own warmed program, so the zero-recompile contract holds).
    ``strict=True`` restores the legacy erroring contract for callers
    that sized their admission path to the ladder."""
    for r in ladder:
        if n <= r:
            return r
    if strict:
        raise ValueError(f"occupancy {n} exceeds top rung {ladder[-1]}")
    return ladder[-1]


class _Staging:
    """The reused host staging buffer of every dense bucket of one
    ``(rows, chunk_size)``, sized for the top rung.  Fires are serial
    and ``_run`` returns only once the result is on the host, so the
    previous stack's H2D has finished before the next pack.  ``dirty``
    counts the leading rows that may still hold an earlier fire's
    payloads; every row past it is zero."""

    __slots__ = ("buf", "dirty")

    def __init__(self, top: int, rows: int, chunk_size: int) -> None:
        self.buf = np.empty((top, rows, chunk_size), np.uint8)
        # written, not calloc'd: every page is faulted in here, at
        # allocation, instead of inside the first fire
        self.buf.fill(0)
        self.dirty = 0

    def pack(self, reqs, rung: int) -> np.ndarray:
        """The rung-shaped stack: ``reqs``' payloads (their leading
        ``rows`` rows: a generic decode's first k survivors), then
        zero pad rows (only the stale ones among them are written)."""
        n = len(reqs)
        stack = self.buf[:rung]
        rows = stack.shape[1]
        for i, r in enumerate(reqs):
            stack[i] = r.payload[:rows]
        if self.dirty > n:
            stack[n:self.dirty] = 0
        if self.dirty <= rung:
            self.dirty = n
        return stack


class _Bucket:
    """One shape bucket: same plugin/profile/op/pattern/chunk-size —
    same device program family.  A ``generic`` decode bucket holds
    every pattern with ``n_erased`` losses (``available`` and
    ``erased`` empty; ``rows`` = k)."""

    __slots__ = ("key", "ec", "op", "available", "erased", "chunk_size",
                 "rows", "requests", "generic", "n_erased")

    def __init__(self, key, ec, op, available, erased, chunk_size,
                 rows, generic: bool = False, n_erased: int = 0) -> None:
        self.key = key
        self.ec = ec
        self.op = op
        self.available = available
        self.erased = erased
        self.chunk_size = chunk_size
        self.rows = rows
        self.generic = generic
        self.n_erased = n_erased
        self.requests: List[EcRequest] = []

    @property
    def oldest_deadline(self) -> float:
        return min(r.deadline for r in self.requests)


class ContinuousBatcher:
    """Coalesce an admission queue into shape-bucketed dispatches.

    ``executor``: ``"device"`` fires the jitted
    ``serve_dispatch_call`` programs; ``"host"`` runs the numpy batch
    surfaces (plugin instances pinned off the XLA path) — the
    zero-compile bookkeeping tier.

    ``service_model``: optional ``(bucket, rung) -> seconds``
    deterministic service-time simulator.  When set, the clock is
    advanced by the model after each dispatch instead of measuring
    wall time — a seeded scenario on a FakeClock then produces
    byte-identical batch compositions AND SLO reports across runs
    (the determinism contract tests/test_serve.py pins).
    """

    def __init__(self, clock=None,
                 ladder: Optional[Tuple[int, ...]] = None,
                 executor: str = "device",
                 service_model: Optional[Callable] = None,
                 min_slack: float = _MIN_SLACK) -> None:
        from ..utils.retry import SystemClock

        if ladder is None:
            # the autotuner's seam: the tuned rung ladder when a
            # best-config table is installed, LADDER otherwise (an
            # explicit ladder — scenario specs, tests — always wins)
            ladder = tuned_ladder()
        if executor not in ("device", "host"):
            raise ValueError(f"executor {executor!r} must be "
                             f"device|host")
        if tuple(ladder) != tuple(sorted(set(ladder))):
            raise ValueError(f"ladder {ladder} must be strictly "
                             f"increasing")
        self.clock = clock if clock is not None \
            else default_clock("serve.batcher.ContinuousBatcher",
                               SystemClock)
        self.ladder = tuple(ladder)
        self.executor = executor
        self.service_model = service_model
        self.min_slack = min_slack
        self._instances: Dict[tuple, object] = {}
        self._buckets: "Dict[tuple, _Bucket]" = {}
        # distinct (bucket key, rung) programs this stream exercised
        self._programs: set = set()
        self._est: Dict[tuple, float] = {}
        # (rows, chunk_size) -> the dense path's reused staging buffer
        self._staging: "Dict[tuple, _Staging]" = {}
        # per-dispatch composition log (bucket key, rung, req ids) —
        # the byte-identical-replay witness tests and the demo print
        self.dispatch_log: List[dict] = []
        self.dispatches = 0
        self.stripes = 0
        self.padded_stripes = 0
        self.padded_bytes = 0
        self.warmup_dispatches = 0

    # -- plugin instance + bucket resolution ----------------------------

    def _instance(self, plugin: str, profile: Dict[str, str]):
        pkey = (plugin, tuple(sorted((str(k), str(v))
                                     for k, v in profile.items())))
        ec = self._instances.get(pkey)
        if ec is None:
            from ..codes.registry import ErasureCodePluginRegistry

            ec = ErasureCodePluginRegistry.instance().factory(
                plugin, dict(profile))
            if self.executor == "host":
                # pin the numpy reference path: the host tier must
                # never dispatch through jax at any batch size
                ec.min_xla_bytes = float("inf")
            self._instances[pkey] = ec
        return ec

    @staticmethod
    def _generic(ec, req: EcRequest) -> bool:
        """Whether ``req`` rides a pattern-generic decode bucket."""
        from ..codes.engine import pattern_generic_decode

        return (req.op == "decode"
                and len(req.available) >= ec.get_data_chunk_count()
                and pattern_generic_decode(ec))

    def bucket_key(self, req: EcRequest) -> tuple:
        """The bucket identity — the PatternCache key of the program
        the bucket will fire, extended with the chunk size (the only
        shape axis the program key doesn't fix).  That key holds the
        erasure pattern, except for a pattern-generic decode, whose
        program is keyed by the erasure count alone."""
        from ..codes.engine import generic_decode_key, pattern_key

        ec = self._instance(req.plugin, req.profile)
        chunk = ec.get_chunk_size(req.stripe_size)
        if self._generic(ec, req):
            return generic_decode_key(ec, len(req.erased), extra=(chunk,))
        return pattern_key(ec, f"serve-{req.op}", req.available,
                           req.erased, extra=(chunk,))

    def _bucket_for(self, req: EcRequest) -> _Bucket:
        key = self.bucket_key(req)
        b = self._buckets.get(key)
        if b is None:
            ec = self._instance(req.plugin, req.profile)
            chunk = ec.get_chunk_size(req.stripe_size)
            if self._generic(ec, req):
                b = _Bucket(key, ec, req.op, (), (), chunk,
                            ec.get_data_chunk_count(), generic=True,
                            n_erased=len(req.erased))
            else:
                rows = (ec.get_data_chunk_count() if req.op == "encode"
                        else len(req.available))
                b = _Bucket(key, ec, req.op, req.available, req.erased,
                            chunk, rows)
            self._buckets[key] = b
        return b

    # -- admission -------------------------------------------------------

    def admit(self, requests: List[EcRequest]) -> List[EcResult]:
        """Classify requests into buckets; a bucket reaching the top
        rung fires immediately (continuous batching — full buckets
        never wait for the next poll).  Each request is stamped
        ``drained`` with one clock read: its batch wait runs from
        here to its fire."""
        if not requests:
            return []
        now = self.clock.monotonic()
        for req in requests:
            req.drained = now
        results: List[EcResult] = []
        with span("serve.admit", metric="serve_admit_seconds"):
            for req in requests:
                b = self._bucket_for(req)
                want = (len(req.available) if b.generic else b.rows,
                        b.chunk_size)
                if tuple(req.payload.shape) != want:
                    raise ValueError(
                        f"request {req.req_id}: payload shape "
                        f"{tuple(req.payload.shape)} != {want} for "
                        f"op={req.op} plugin={req.plugin}")
                b.requests.append(req)
                if req.trace is not None:
                    # the request→bucket link: bucket identity ≡ device-
                    # program identity, so the trace names the program
                    # family it will ride before the batch even fires
                    req.trace.add("bucket", self.clock.monotonic(),
                                  bucket="|".join(str(p) for p in b.key),
                                  pending=len(b.requests))
                if len(b.requests) >= self.ladder[-1]:
                    results += self._fire(b, "full")
        return results

    # -- deadline-aware firing ------------------------------------------

    def est_service(self, key: tuple) -> float:
        """EWMA service-time estimate for the bucket's dispatches
        (seeded by the timed warmup dispatch, 0.0 for a bucket that
        never warmed)."""
        return self._est.get(key, 0.0)

    def _margin(self, key: tuple) -> float:
        """How far BEFORE its deadline a bucket must fire: twice the
        service estimate plus the floor.  Firing at exactly
        ``deadline - est`` puts every completion on the knife edge
        (any estimate error = a miss); the 2x margin lands the
        completion ~one service time early instead."""
        return 2.0 * self.est_service(key) + self.min_slack

    def _due(self, b, now: float) -> bool:
        if not b.requests:
            return False
        return b.oldest_deadline - now - self._margin(b.key) <= 0.0

    def poll(self, queue: Optional[AdmissionQueue] = None
             ) -> List[EcResult]:
        """One batcher turn: drain the queue, fire full buckets, then
        fire every bucket whose oldest request's slack has run out —
        earliest deadline first, so a tight-deadline bucket never
        queues behind a lazy one."""
        results: List[EcResult] = []
        if queue is not None:
            results += self.admit(queue.drain())
        now = self.clock.monotonic()
        due = sorted((b for b in self._buckets.values()
                      if self._due(b, now)),
                     key=lambda b: b.oldest_deadline)
        for b in due:
            results += self._fire(b, "deadline")
        return results

    def flush(self) -> List[EcResult]:
        """Fire every non-empty bucket (end of stream)."""
        results: List[EcResult] = []
        for b in sorted((b for b in self._buckets.values() if b.requests),
                        key=lambda b: b.oldest_deadline):
            results += self._fire(b, "flush")
        return results

    def next_wakeup(self) -> Optional[float]:
        """Earliest absolute time any bucket becomes due (the sim
        driver advances its FakeClock here when idle)."""
        times = [b.oldest_deadline - self._margin(b.key)
                 for b in self._buckets.values() if b.requests]
        return min(times) if times else None

    def pending(self) -> int:
        return sum(len(b.requests) for b in self._buckets.values())

    # -- dispatch --------------------------------------------------------

    def _staging_for(self, b: _Bucket) -> _Staging:
        """The bucket's shared staging buffer, allocated on first use
        (at warm-up, for a warmed bucket)."""
        shape = (b.rows, b.chunk_size)
        st = self._staging.get(shape)
        outcome = "reused"
        if st is None:
            st = self._staging[shape] = _Staging(self.ladder[-1], *shape)
            outcome = "allocated"
        tel.counter("serve_pack_buffers", op=b.op, outcome=outcome)
        return st

    def _launch(self, b: _Bucket, stack: np.ndarray, coef=None):
        """Start one batched execution: the jitted serve program
        (device; the result stays on the device until fetched) or the
        numpy batch surfaces (host).  ``coef``: a generic decode
        bucket's ``(rung, e, k)`` coefficient operand."""
        self._programs.add((b.key, stack.shape[0]))
        if self.executor == "device":
            from ..codes.engine import (serve_decode_generic,
                                        serve_dispatch_call)

            if b.generic:
                return serve_decode_generic(b.ec, b.n_erased)(stack, coef)
            call = serve_dispatch_call(b.ec, b.op, b.available, b.erased)
            return call(stack)
        # host tier: numpy end to end (the trace still names the
        # program family it rode — "host:" tier, so a host-executor
        # trace joins nothing in attribution_rows but stays honest
        # about where the bytes were computed)
        if tracing.enabled():
            tracing.note_program(
                "serve.host", {"op": b.op,
                               "plugin": type(b.ec).__name__})
        if b.generic:
            return b.ec.decode_chunks_operand_batch(stack, coef)
        if b.op == "encode":
            return np.asarray(b.ec.encode_chunks_batch(stack))
        if b.op == "decode":
            return np.asarray(b.ec.decode_chunks_batch(
                stack, b.available, b.erased))
        return _host_repair(b.ec, stack, b.available, b.erased)

    def _execute(self, b: _Bucket, stack: np.ndarray, coef=None):
        """One batched execution, returned as op-shaped host arrays."""
        return _host_arrays(self._launch(b, stack, coef))

    def _coefficients(self, b: _Bucket, reqs: List[EcRequest], rung: int):
        """A generic decode fire's coefficient operand, one row per
        stripe of the rung, zero where no request is; None for any
        other bucket.  Observes the fire's distinct erasure patterns
        (``serve_dispatch_patterns``) for every decode/repair fire."""
        if b.op == "encode":
            return None
        if not b.generic:
            tel.observe("serve_dispatch_patterns", 1, op=b.op)
            return None
        from ..codes.engine import decode_table

        k = b.rows
        patterns: Dict[tuple, int] = {}
        index = np.full(rung, -1, np.int64)
        for i, r in enumerate(reqs):
            index[i] = patterns.setdefault(
                (tuple(r.available[:k]), r.erased), len(patterns))
        tel.observe("serve_dispatch_patterns", len(patterns), op=b.op)
        return decode_table(b.ec, list(patterns), index, rung)

    def _run(self, op: str, launch: Callable):
        """The dispatch stages of one fire: ``serve.dispatch`` around
        the launch, then (device executor) ``serve.wait`` for the
        result and ``serve.d2h`` for its copy to the host."""
        with span("serve.dispatch", executor=self.executor):
            out = launch()
        if self.executor != "device":
            return out
        import jax

        with span("serve.wait", metric="serve_device_wait_seconds",
                  op=op):
            jax.block_until_ready(out)
        with span("serve.d2h", metric="serve_d2h_seconds", op=op):
            return _host_arrays(out)

    def _fire(self, b: _Bucket, reason: str) -> List[EcResult]:
        """Fire a bucket; occupancy above the top rung (an oversized
        admission burst) is split into top-rung slices — every slice
        rides an already-warmed program, so the legacy hard error is
        gone without any new shape."""
        reqs, b.requests = b.requests, []
        results: List[EcResult] = []
        top = self.ladder[-1]
        while reqs:
            take, reqs = reqs[:top], reqs[top:]
            results += self._fire_slice(b, take, reason)
        return results

    def _fire_slice(self, b: _Bucket, reqs: List[EcRequest],
                    reason: str) -> List[EcResult]:
        n = len(reqs)
        rung = rung_for(n, self.ladder)
        with span("serve.batch", op=b.op, occupancy=n, rung=rung,
                  plugin=type(b.ec).__name__, reason=reason):
            with span("serve.pack", metric="serve_pack_seconds",
                      op=b.op):
                stack = self._staging_for(b).pack(reqs, rung)
            coef = self._coefficients(b, reqs, rung)
            traced = (tracing.enabled()
                      and any(r.trace is not None for r in reqs))
            if traced:
                tracing.clear_program()
            t0 = self.clock.monotonic()
            out = self._run(b.op, lambda: self._launch(b, stack, coef))
            if self.service_model is not None:
                # sim mode: deterministic service time instead of wall
                # time — byte-identical SLO reports from a seed
                self.clock.sleep(self.service_model(b, rung))
            t1 = self.clock.monotonic()
            service = t1 - t0
            if _SERVICE_JITTER is not None:
                service = _SERVICE_JITTER(service, self.dispatches)
            self._est[b.key] = (service if b.key not in self._est else
                                (1 - _EWMA_ALPHA) * self._est[b.key]
                                + _EWMA_ALPHA * service)
            self.dispatches += 1
            self.stripes += n
            pad = rung - n
            self.padded_stripes += pad
            self.padded_bytes += pad * b.rows * b.chunk_size
            tel.counter("serve_dispatches", op=b.op, reason=reason)
            tel.counter("serve_stripes", n, op=b.op)
            tel.observe("serve_batch_occupancy", n, op=b.op)
            self.dispatch_log.append({
                "bucket": "|".join(str(p) for p in b.key),
                "op": b.op, "occupancy": n, "rung": rung,
                "req_ids": [r.req_id for r in reqs]})
            with span("serve.demux", metric="serve_demux_seconds",
                      op=b.op):
                results = []
                for i, r in enumerate(reqs):
                    if b.op == "repair":
                        rec, parity = out
                        payload_out = (rec[i], parity[i])
                    else:
                        payload_out = out[i]
                    results.append(self._result(
                        r, payload_out, b.op, t0, t1, service, n, rung))
        if traced:
            # the fire decision + the program the batch rode + the
            # per-request demux completion, stamped on the SAME clock
            # as the SLO ledger (on a FakeClock t_done == t1 — demux
            # is host bookkeeping, charged only on the real clock)
            program = tracing.take_program()
            batch_seq = self.dispatches - 1
            t_done = self.clock.monotonic()
            for r, res in zip(reqs, results):
                tr = r.trace
                if tr is None:
                    continue
                tr.add("fire", t0, occupancy=n, rung=rung,
                       batch_seq=batch_seq, executor=self.executor,
                       co_batched=[q.req_id for q in reqs])
                if program is not None:
                    tr.add("program", t0, series=program)
                tr.add("dispatch_end", t1)
                tr.add("done", t_done,
                       deadline_met=res.deadline_met)
        return results

    @staticmethod
    def _result(r: EcRequest, output, op: str, t0: float, t1: float,
                service: float, n: int, rung: int) -> EcResult:
        """One request's result; observes its queue wait (arrival to
        dispatch start ``t0``) and the part of it spent in a bucket
        (drained to ``t0``)."""
        wait = max(0.0, t0 - (r.arrival if r.arrival is not None
                              else t0))
        tel.observe("serve_queue_wait_seconds", wait, op=op)
        if r.drained is not None:
            tel.observe("serve_batch_wait_seconds",
                        max(0.0, t0 - r.drained), op=op)
        return EcResult(
            request=r, output=output, completed=t1, queue_wait=wait,
            service=service, batch_occupancy=n, batch_rung=rung,
            deadline_met=(r.deadline is None or t1 <= r.deadline))

    # -- warmup ----------------------------------------------------------

    def warmup(self, requests: List[EcRequest]) -> int:
        """Compile the whole bucket ladder for every distinct bucket
        the request list will touch: one zero-filled dispatch per
        (bucket, rung).  After this, a stream drawn from the same mix
        compiles NOTHING — the armed recompile budget and the compile
        monitor both stay flat (the acceptance gate's 'zero warm
        recompiles').  The dispatches run on zeroed slices of the
        bucket's staging buffer, so the buffer is allocated here and
        not in the measured stream.  Returns the number of warmup
        dispatches.

        A pattern-generic decode bucket is one bucket for all its
        patterns, so it warms |ladder| programs once."""
        seen = set()
        fired = 0
        for req in requests:
            key = self.bucket_key(req)
            if key in seen:
                continue
            seen.add(key)
            b = self._bucket_for(req)
            st = self._staging_for(b)
            for rung in self.ladder:
                self._execute(b, st.pack((), rung), self._zero_coef(b, rung))
                fired += 1
            # seed the service estimator with a timed WARM dispatch of
            # the top rung (the first run above paid the compile, so
            # this measures steady-state service, not trace time) —
            # deadline-slack firing then has an honest worst-case
            # estimate before the first real request is at stake.  In
            # sim mode the model is the estimator; skip the extra
            # dispatch and don't touch the sim clock.
            if self.service_model is not None:
                self._est[key] = self.service_model(b, self.ladder[-1])
            else:
                top = st.pack((), self.ladder[-1])
                coef = self._zero_coef(b, self.ladder[-1])
                t0 = self.clock.monotonic()
                self._execute(b, top, coef)
                self._est[key] = self.clock.monotonic() - t0
                fired += 1
        self.warmup_dispatches += fired
        if fired:
            dout("serve", 10,
                 f"warmed {len(seen)} buckets x {len(self.ladder)} "
                 f"rungs ({fired} dispatches)")
        return fired

    @staticmethod
    def _zero_coef(b, rung: int):
        """A warm-up fire's coefficient operand (generic buckets)."""
        if not b.generic:
            return None
        return np.zeros((rung, b.n_erased, b.rows), np.uint8)

    # -- accounting ------------------------------------------------------

    def cached_program_count(self) -> int:
        """Distinct programs this batcher's stream exercised: (bucket,
        rung) pairs (every rung is its own XLA program)."""
        return len(self._programs)

    def padding_stats(self) -> dict:
        total = self.stripes + self.padded_stripes
        return {
            "dispatches": self.dispatches,
            "stripes": self.stripes,
            "padded_stripes": self.padded_stripes,
            "padded_bytes": self.padded_bytes,
            "padding_overhead": (round(self.padded_stripes / total, 6)
                                 if total else 0.0),
            "warmup_dispatches": self.warmup_dispatches,
            "cached_programs": self.cached_program_count(),
        }


def _host_arrays(out):
    """A dispatch result as host arrays (repair returns a pair)."""
    if isinstance(out, (tuple, list)):
        return tuple(np.asarray(o) for o in out)
    return np.asarray(out)


def _host_repair(ec, stack: np.ndarray, available: Tuple[int, ...],
                 erased: Tuple[int, ...]):
    """Numpy mirror of engine.fused_repair_call: decode the erased
    shards, assemble the data chunks from survivor and decoded columns
    by static index, re-encode the full parity set.  Byte-identical to
    the fused device program by construction (same surfaces, same
    column assembly)."""
    from ..codes.stripe import _chunk_mapping

    rec = np.asarray(ec.decode_chunks_batch(stack, available, erased))
    mapping = _chunk_mapping(ec)
    aidx = {s: t for t, s in enumerate(available)}
    eidx = {s: t for t, s in enumerate(erased)}
    cols = []
    for c in range(ec.get_data_chunk_count()):
        shard = mapping[c]
        if shard in aidx:
            cols.append(stack[:, aidx[shard], :])
        elif shard in eidx:
            cols.append(rec[:, eidx[shard], :])
        else:
            raise IOError(
                f"data shard {shard} neither available nor erased "
                f"(avail={available}, erased={erased})")
    data = np.stack(cols, axis=1)
    parity = np.asarray(ec.encode_chunks_batch(data))
    return rec, parity
