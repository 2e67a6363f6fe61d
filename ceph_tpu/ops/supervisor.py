"""The supervised dispatch plane — ONE choke point between every
host-side dispatch seam and the device backend, so a backend that
fails AFTER warm-up (device loss, HBM OOM, hang,
corrupted output buffer) is classified and survived instead of
propagated.

Before this module the repo handled backend failure only at two
*startup* moments: ``ops/fallback.py`` probed the backend once
("backend identity cannot change mid-process") and
``parallel/plane.py`` degraded only at mesh formation.  A dispatch
that started failing mid-run had no classified path — exactly the
failure mode a fleet serving millions of users hits daily.  The
supervisor closes it.  Every device-dispatch seam —
``engine.fused_repair_call``, ``engine.serve_dispatch_call``,
``apply_matrix_best`` / ``apply_matrix_packed_best``,
``crush/bulk.bulk_do_rule`` and their mesh/sharded variants — routes
its eager calls through :meth:`DispatchSupervisor.dispatch`, which
classifies failures and applies the matching response:

==================  ==================================================
classification      response
==================  ==================================================
transient error     bounded ``utils/retry`` backoff (injectable
                    clock, decorrelated-jitter-capable policy)
RESOURCE_EXHAUSTED  batch-rung downshift: split the stripe batch in
                    half and redispatch the halves (recursively, to
                    rung 1), outputs re-concatenated byte-identically
persistent loss     LIVE ``FallbackPolicy.demote()`` down the
                    pallas → xla → numpy ladder with probe-cache
                    invalidation + PatternCache clear; at the numpy
                    floor the seam's ground-truth twin completes the
                    dispatch byte-identically
mesh-member loss    device quarantine: the data plane reshrinks
                    8 → 4 → 2 → 1 → single-device (never silently to
                    host) and the seam's sharded program rebuilds
host loss           host quarantine (ISSUE 17): the plane reshrinks
                    HOST-granular — hosts 4 → 2 → 1 (every device the
                    lost domain contributed at once), then the device
                    ladder inside the survivor — and in-flight intents
                    journaled for the lost host replay epoch-fenced
                    onto the shrunken plane (``set_inflight_reclaim``)
hang                clock-injectable dispatch deadline; a dispatch
                    that burns past it is classified as backend loss
output corruption   (self-verify mode) outputs are CRC-checked
                    against the numpy ground truth; a mismatch is
                    reclassified as a backend fault, flight-recorded,
                    and the dispatch re-runs on a demoted tier — the
                    corrupted bytes are NEVER returned
==================  ==================================================

Every demotion/quarantine is paired with a **health probe**: after
``promote_after`` consecutive clean probes (the chaos plan cleared,
the backend probe answers again) the supervisor re-promotes — policy
tiers pop back up the ladder, the plane restores its original width,
and the PatternCache clears so programs rebuild on the recovered
tier.  Demote, quarantine and re-promote each emit a telemetry
counter + structured event AND freeze a flight-recorder post-mortem
(telemetry/recorder.py), so a mid-run outage is a diagnosable
artifact, not a stack trace.

Every supervised outcome is **byte-identical to the unfailed run** by
construction: every tier of every seam is byte-identical (pinned
across tests/), so retry, split, demoted completion and ground-truth
twins all return the same bytes.

Chaos: ``chaos/dispatch.py`` arms seeded ``DispatchFault`` plans per
``(seam, Nth call)`` — the supervisor polls the plan once per dispatch
attempt, so a (seed, faults) pair replays byte-identically.  See
docs/ROBUSTNESS.md "Supervised dispatch plane".
"""

from __future__ import annotations

import os
import zlib
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from ..utils.errors import (ProbeTimeout, RetryExhausted,
                            TransientBackendError)
from ..utils.log import dout
from ..utils.detcheck import default_clock
from ..utils.retry import RetryPolicy, SystemClock, retry_call
from ..utils.locks import make_lock

# message markers for classifying REAL backend errors (jaxlib's
# XlaRuntimeError subclasses RuntimeError; PJRT surfaces gRPC-style
# status names in the message)
_OOM_MARKERS = ("resource_exhausted", "out of memory", "hbm oom")
_LOSS_MARKERS = ("unavailable", "backend", "connection",
                 "socket closed", "deadline_exceeded",
                 "failed_precondition")
# a whole fault domain gone, not one chip: PJRT/coordination-service
# phrasings for a peer process dropping out (checked BEFORE the
# generic loss markers — "host unreachable" also contains no generic
# marker, but a mixed message must classify at host granularity)
_HOST_MARKERS = ("host unreachable", "worker lost", "peer down",
                 "process exited", "coordination service",
                 "slice health")

# escalation ceiling per dispatch: transient-exhausted -> demote(xla)
# -> demote(numpy)/quarantine ladder can never loop
_MAX_ESCALATIONS = 6
_MAX_SPLIT_DEPTH = 8
_DEFAULT_HANG_S = 1.0  # tpu-lint: disable=gf-float -- hang deadline seconds, not GF math

_HOST = object()        # _escalate verdict: complete on the host twin


def classify_dispatch_error(e: BaseException) -> Optional[str]:
    """Map a dispatch-seam exception to a supervised class —
    ``"transient"`` / ``"oom"`` / ``"backend_loss"`` /
    ``"host_loss"`` — or None for errors that are NOT the backend's
    fault (a shape error, a plugin contract violation): those
    propagate untouched, because retrying or demoting a genuine bug
    would only hide it."""
    from ..chaos.dispatch import (DispatchHang, InjectedBackendLoss,
                                  InjectedOom)
    from ..chaos.hosts import InjectedHostLoss, InjectedHostPartition
    if isinstance(e, RetryExhausted):
        inner = (classify_dispatch_error(e.last)
                 if e.last is not None else None)
        return inner or "transient"
    if isinstance(e, TransientBackendError):
        return "transient"
    if isinstance(e, InjectedOom):
        return "oom"
    if isinstance(e, (InjectedHostLoss, InjectedHostPartition)):
        return "host_loss"
    if isinstance(e, ProbeTimeout):
        # a probe that burned its deadline is the HANG class (the
        # probed endpoint is wedged, not flaky): backend loss, so the
        # ladder acts — never the transient retry loop
        return "backend_loss"
    if isinstance(e, (InjectedBackendLoss, DispatchHang)):
        return "backend_loss"
    if isinstance(e, (RuntimeError, OSError, ConnectionError)):
        msg = str(e).lower()
        if any(m in msg for m in _OOM_MARKERS):
            return "oom"
        if any(m in msg for m in _HOST_MARKERS):
            return "host_loss"
        if any(m in msg for m in _LOSS_MARKERS):
            return "backend_loss"
    return None


def _crc_output(out) -> int:
    """crc32 over the host bytes of one dispatch output (array or
    tuple of arrays) — the self-verify sample."""
    parts = out if isinstance(out, (tuple, list)) else (out,)
    c = 0
    for p in parts:
        c = zlib.crc32(np.ascontiguousarray(np.asarray(p)).tobytes(),
                       c)
    return c


def _concat_outputs(lo, hi):
    """Re-join a split redispatch along the batch axis,
    component-wise for multi-output seams."""
    if isinstance(lo, (tuple, list)):
        return tuple(np.concatenate([np.asarray(a), np.asarray(b)],
                                    axis=0)
                     for a, b in zip(lo, hi))
    return np.concatenate([np.asarray(lo), np.asarray(hi)], axis=0)


class DispatchSupervisor:
    """The process dispatch supervisor (swap via
    :func:`set_global_supervisor` in tests; the selftest builds fully
    isolated instances).

    - ``clock``: injectable (FakeClock in tests) — backoff sleeps,
      hang deadlines and probe pacing all run on it.
    - ``deadline_s``: dispatch deadline for hang classification
      (``CEPH_TPU_DISPATCH_DEADLINE`` env; None = no hang detection).
    - ``self_verify``: CRC-sample every ``verify_every``-th supervised
      output against the numpy ground-truth twin
      (``CEPH_TPU_SELF_VERIFY=1``); detected corruption is
      reclassified as a backend fault and never returned.
    - ``promote_after``: consecutive clean health probes before a
      demoted tier / quarantined plane re-promotes.
    - ``policy`` / ``cache_clear`` / ``plane_ctl``: injectable process
      couplings (the global FallbackPolicy, the engine PatternCache
      clear, the data-plane reshrink) so the audit selftest runs on
      isolated state.
    """

    def __init__(self, clock=None, retry_policy: Optional[RetryPolicy]
                 = None, deadline_s: Optional[float] = None,
                 self_verify: Optional[bool] = None,
                 verify_every: int = 1, promote_after: int = 3,
                 probe_every: int = 4,
                 policy=None,
                 cache_clear: Optional[Callable[[], None]] = None,
                 plane_ctl: bool = True) -> None:
        self.clock = clock if clock is not None \
            else default_clock("ops.supervisor.DispatchSupervisor",
                               SystemClock)
        self.retry_policy = retry_policy or RetryPolicy(
            attempts=3, base_delay=0.002,  # tpu-lint: disable=gf-float -- backoff seconds, not GF math
            multiplier=2.0,  # tpu-lint: disable=gf-float -- backoff multiplier, not GF math
            max_delay=0.05)  # tpu-lint: disable=gf-float -- backoff seconds, not GF math
        if deadline_s is None:
            env = os.environ.get("CEPH_TPU_DISPATCH_DEADLINE",
                                 "").strip()
            deadline_s = float(env) if env else None  # tpu-lint: disable=gf-float -- wall-clock seconds, not GF math
        self.deadline_s = deadline_s
        if self_verify is None:
            self_verify = os.environ.get(
                "CEPH_TPU_SELF_VERIFY", "").strip().lower() in (
                    "1", "on", "true", "yes")
        self.self_verify = self_verify
        self.verify_every = max(1, verify_every)
        self.promote_after = max(1, promote_after)
        self.probe_every = max(1, probe_every)
        self._policy_override = policy
        self._cache_clear_override = cache_clear
        self._plane_ctl = plane_ctl
        self._lock = make_lock("ops.supervisor.DispatchSupervisor._lock")
        # demotion state (what re-promotion must restore)
        self._floor: Optional[str] = None      # "numpy" once demoted
        self._tier_demotions = 0
        self._plane_width0: Optional[int] = None
        self._plane_hosts0: Optional[int] = None
        self._clean_probes = 0
        self._since_probe = 0
        self._verify_seq = 0
        # journal-backed in-flight reclaim (ISSUE 17): the recovery
        # layer registers a callback that replays the lost host's
        # intent records onto the shrunken plane after a host
        # quarantine (set_inflight_reclaim)
        self._inflight_reclaim: Optional[Callable[[str], int]] = None
        self.counters: Dict[str, int] = {
            "dispatches": 0, "retries": 0, "rung_downshifts": 0,
            "demotions": 0, "quarantines": 0, "repromotions": 0,
            "host_quarantines": 0, "host_repromotions": 0,
            "journal_redispatches": 0,
            "hangs": 0, "slow_dispatches": 0, "host_completions": 0,
            "verify_failures": 0, "verified_clean": 0,
            "injected_faults": 0, "probe_clean": 0, "probe_failed": 0,
        }

    # -- injectable couplings --------------------------------------------

    def _policy(self):
        if self._policy_override is not None:
            return self._policy_override
        from .fallback import global_policy
        return global_policy()

    def _cache_clear(self) -> None:
        if self._cache_clear_override is not None:
            self._cache_clear_override()
            return
        from ..codes.engine import global_pattern_cache
        global_pattern_cache().clear()

    def _count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    # -- state -----------------------------------------------------------

    @property
    def demoted(self) -> bool:
        with self._lock:
            return (self._tier_demotions > 0
                    or self._plane_width0 is not None)

    def stats(self) -> dict:
        with self._lock:
            out = dict(self.counters)
            out["demoted"] = (self._tier_demotions > 0
                              or self._plane_width0 is not None)
            out["tier_floor"] = self._floor
            out["tier_demotions"] = self._tier_demotions
            out["plane_width0"] = self._plane_width0
            out["plane_hosts0"] = self._plane_hosts0
            out["clean_probes"] = self._clean_probes
        return out

    def set_inflight_reclaim(
            self, cb: Optional[Callable[[str], int]]
    ) -> Optional[Callable[[str], int]]:
        """Register the journal-backed in-flight reclaim hook: after a
        host quarantine, ``cb(seam)`` replays the lost host's intent
        records (recovery/journal.py, epoch-fenced) onto the shrunken
        plane and returns how many ops were re-dispatched.  Returns
        the previous hook so callers can restore it."""
        with self._lock:
            prev = self._inflight_reclaim
            self._inflight_reclaim = cb
        return prev

    def reset_pacing(self) -> None:
        """Zero the probe/verify pacing counters WITHOUT touching the
        cumulative counters or demotion state — the scenario runner
        calls this when it arms a device-plane chaos plan, so a
        seeded run's tick cadence (and therefore its report) is
        independent of whatever supervised work ran earlier in the
        process (byte-identical replay)."""
        with self._lock:
            self._since_probe = 0
            self._verify_seq = 0
            self._clean_probes = 0

    def reset(self) -> None:
        """Forget demotion state and zero counters (tests)."""
        with self._lock:
            for k in self.counters:
                self.counters[k] = 0
            self._floor = None
            self._tier_demotions = 0
            self._plane_width0 = None
            self._plane_hosts0 = None
            self._clean_probes = 0
            self._since_probe = 0
            self._verify_seq = 0

    # -- THE choke point -------------------------------------------------

    def dispatch(self, seam: str, fn: Callable, args: Tuple, *,
                 host_fn: Optional[Callable] = None,
                 rebuild: Optional[Callable] = None,
                 splittable: bool = True,
                 verifiable: bool = True,
                 _depth: int = 0):
        """Run one supervised device dispatch: ``fn(*args)`` with the
        full classification ladder above it.

        ``host_fn(*args)`` is the seam's numpy ground-truth twin
        (byte-identical by construction) — the numpy-floor completion
        path and the self-verify reference.  ``rebuild()`` re-derives
        the dispatch callable after a tier demotion or plane reshrink
        (the engine seams pass their own cached-call constructors, so
        a rebuilt program lands on the demoted tier / shrunk plane).
        ``splittable``: the first argument carries the stripe batch on
        axis 0, so an OOM can downshift the rung by splitting it (with
        every argument of the same leading size).
        ``verifiable=False`` opts the seam out of self-verify — for
        seams whose device output legitimately differs from the
        reference twin (crush bulk's need-host residue flags feed a
        ladder the exact host mapper resolves in one step).
        """
        from ..chaos.dispatch import active_plan
        from ..chaos.hosts import active_host_plan
        self._count("dispatches")
        plan = active_plan()
        if self._floor == "numpy" and host_fn is not None:
            # the backend is gone: the seam call still advances the
            # chaos plans' windows (so a timed fault can clear), then
            # the ground-truth twin completes the dispatch.  hosts=0:
            # there is no plane to land on, so a host fault cannot
            # fire — but flap timelines stay aligned
            if plan is not None:
                plan.poll(seam)
            hplan = active_host_plan()
            if hplan is not None:
                hplan.poll(seam, 0)
            out = self._host_complete(seam, host_fn, args)
            self._after_dispatch()
            return out
        cur_fn = fn
        escalations = 0
        last_err: Optional[BaseException] = None
        while True:
            try:
                out = self._run_with_retry(seam, cur_fn, args, plan)
                break
            except BaseException as e:  # noqa: BLE001 — classified,
                # unclassified errors re-raise immediately below
                cls = classify_dispatch_error(e)
                if cls is None:
                    raise
                last_err = e
                if cls == "oom":
                    b = self._batch_of(args)
                    if (splittable and b is not None and b > 1
                            and _depth < _MAX_SPLIT_DEPTH):
                        return self._split_redispatch(
                            seam, cur_fn, args, host_fn=host_fn,
                            rebuild=rebuild, verifiable=verifiable,
                            depth=_depth)
                    # nothing left to split: the device genuinely
                    # cannot hold rung 1 — treat as backend loss
                escalations += 1
                if escalations > _MAX_ESCALATIONS:
                    raise
                verdict = self._escalate(seam, e, cur_fn,
                                         rebuild=rebuild,
                                         host_fn=host_fn, cls=cls)
                if verdict is _HOST:
                    out = self._host_complete(seam, host_fn, args)
                    break
                cur_fn = verdict
        if verifiable:
            out = self._maybe_self_verify(seam, out, args,
                                          host_fn=host_fn,
                                          rebuild=rebuild, fn=cur_fn)
        self._after_dispatch()
        return out

    # -- attempt layer ---------------------------------------------------

    def _run_with_retry(self, seam, fn, args, plan):
        from ..telemetry import metrics as tel
        from ..telemetry import tracing

        def once():
            self._poll_host_plan(seam)
            fault = plan.poll(seam) if plan is not None else None
            return self._call_once(seam, fn, args, fault, plan)

        def on_retry(_i, delay, e):
            self._count("retries")
            tel.counter("supervisor_retries", seam=seam,
                        error=type(e).__name__)
            if tracing.enabled():
                # on_retry fires BEFORE the backoff sleep, so the
                # interval [now, now+delay] is exactly the clock time
                # this dispatch spent backing off — the analyzer's
                # retry_backoff carve (telemetry/analyzer.py)
                now = self.clock.monotonic()
                tracing.note_retry(seam, now, now + delay,
                                   error=type(e).__name__)

        return retry_call(once, policy=self.retry_policy,
                          clock=self.clock, on_retry=on_retry)

    def _poll_host_plan(self, seam) -> None:
        """One host-fault-plan poll per dispatch attempt: does this
        dispatch land on a host the adversary holds down?  The plan is
        polled with the plane's CURRENT host count, so a fault whose
        host the reshrink already evicted goes quiet — the redispatch
        after a host quarantine completes like the survivors stopped
        routing to the dead host (which is the point)."""
        from ..chaos.hosts import (InjectedHostLoss,
                                   InjectedHostPartition,
                                   active_host_plan)
        hplan = active_host_plan()
        if hplan is None:
            return
        hosts = 1   # no plane: the process itself is one fault domain
        if self._plane_ctl:
            from ..parallel import plane as planemod
            p = planemod.data_plane()
            if p is not None:
                hosts = p.hosts
        fault = hplan.poll(seam, hosts)
        if fault is None:
            return
        self._count("injected_faults")
        if fault.kind == "host_partition":
            raise InjectedHostPartition(
                f"injected partition: host {fault.host} fenced at "
                f"seam {seam!r} — its writes are stale and must be "
                f"epoch-fenced")
        raise InjectedHostLoss(
            f"injected loss of host {fault.host} at seam {seam!r}")

    def _call_once(self, seam, fn, args, fault, plan):
        from ..chaos.dispatch import (DispatchHang,
                                      InjectedBackendLoss, InjectedOom)
        from ..telemetry import metrics as tel
        from ..telemetry import recorder
        if fault is not None:
            self._count("injected_faults")
            if fault.kind == "transient":
                raise TransientBackendError(
                    f"injected transient dispatch error at seam "
                    f"{seam!r}")
            if fault.kind == "oom":
                raise InjectedOom(seam)
            if fault.kind == "backend_loss":
                raise InjectedBackendLoss(
                    f"injected backend loss at seam {seam!r}")
            if fault.kind == "hang":
                dl = self.deadline_s or _DEFAULT_HANG_S
                # the wedged call burns the deadline on the injectable
                # clock, then the supervisor classifies the overrun
                self.clock.sleep(dl * 2)
                self._count("hangs")
                tel.counter("supervisor_hangs", seam=seam)
                raise DispatchHang(
                    f"dispatch at seam {seam!r} exceeded deadline "
                    f"{dl}s (injected hang)")
        t0 = self.clock.monotonic()
        out = fn(*args)
        elapsed = self.clock.monotonic() - t0
        if self.deadline_s is not None and elapsed > self.deadline_s:
            # post-hoc hang detection: the result DID arrive, but a
            # dispatch this slow is a wedging backend — count it and
            # breadcrumb the flight ring so the trend is visible
            self._count("slow_dispatches")
            tel.counter("supervisor_slow_dispatches", seam=seam)
            recorder.note("supervisor_slow", seam=seam,
                          elapsed=round(elapsed, 6),
                          deadline=self.deadline_s)
        if fault is not None and fault.kind == "corrupt":
            out = plan.corrupt_output(fault, seam, out)
        return out

    @staticmethod
    def _batch_of(args) -> Optional[int]:
        if not args:
            return None
        shape = getattr(args[0], "shape", None)
        if not shape:
            return None
        return int(shape[0])

    def _split_redispatch(self, seam, fn, args, *, host_fn, rebuild,
                          verifiable, depth):
        from ..telemetry import metrics as tel
        from ..telemetry import tracing
        stack = args[0]
        b = int(stack.shape[0])
        mid = (b + 1) // 2
        if tracing.enabled():
            tracing.annotate("supervisor_rung_downshift",
                             self.clock.monotonic(), seam=seam,
                             batch=b, split=f"{mid}+{b - mid}")
        self._count("rung_downshifts")
        tel.counter("supervisor_rung_downshifts", seam=seam)
        tel.event("supervisor_rung_downshift", seam=seam, batch=b,
                  split=(mid, b - mid))
        dout("ec", 1, f"supervisor: RESOURCE_EXHAUSTED at {seam}; "
                      f"splitting batch {b} -> {mid}+{b - mid}")
        # every operand that carries the stripe batch on axis 0 (the
        # stack, a per-stripe coefficient operand) splits with it
        per_stripe = [self._batch_of((a,)) == b for a in args]
        halves = []
        for lo, hi in ((0, mid), (mid, b)):
            part = tuple(a[lo:hi] if cut else a
                         for a, cut in zip(args, per_stripe))
            halves.append(self.dispatch(
                seam, fn, part, host_fn=host_fn,
                rebuild=rebuild, splittable=True,
                verifiable=verifiable, _depth=depth + 1))
        return _concat_outputs(halves[0], halves[1])

    # -- escalation ------------------------------------------------------

    def _escalate(self, seam, err, cur_fn, *, rebuild, host_fn,
                  cls=None):
        """Persistent failure: quarantine a whole host fault domain
        (``host_loss`` on a multi-host plane), else a mesh member
        (when a plane is active and the seam can rebuild), else demote
        the backend tier.  Returns the next callable to try, or
        ``_HOST``."""
        if self._plane_ctl and rebuild is not None:
            from ..parallel import plane as planemod
            p = planemod.data_plane()
            if p is not None:
                if cls == "host_loss" and p.hosts > 1:
                    return self._host_quarantine(seam, p, rebuild)
                if p.n_devices > 1:
                    return self._quarantine(seam, p, rebuild)
        return self._demote_tier(seam, err, cur_fn, rebuild=rebuild,
                                 host_fn=host_fn)

    def _host_quarantine(self, seam, p, rebuild):
        """Evict one host fault domain: halve the host count (every
        device the lost domain contributed goes at once), replay the
        lost host's journaled in-flight intents onto the survivor
        plane, and rebuild the seam's program."""
        from ..parallel import plane as planemod
        from ..telemetry import metrics as tel
        from ..telemetry import recorder, tracing
        n_hosts, dph, n = p.hosts, p.devices_per_host, p.n_devices
        if tracing.enabled():
            tracing.annotate("supervisor_host_quarantine",
                             self.clock.monotonic(), seam=seam,
                             from_hosts=n_hosts,
                             from_devices=n)
        with self._lock:
            if self._plane_width0 is None:
                self._plane_width0 = n
            if self._plane_hosts0 is None:
                self._plane_hosts0 = n_hosts
        nxt_h = n_hosts // 2
        nxt = nxt_h * dph
        self._count("host_quarantines")
        tel.counter("supervisor_host_quarantines", seam=seam)
        tel.event("supervisor_host_quarantine", seam=seam,
                  from_hosts=n_hosts, to_hosts=max(nxt_h, 1),
                  from_devices=n, to_devices=max(nxt, 1))
        recorder.trip(
            "host_quarantined",
            f"host fault domain lost at {seam}: plane reshrink "
            f"{n_hosts}x{dph} -> {max(nxt_h, 1)}x{dph} hosts",
            seam=seam, from_hosts=n_hosts, to_hosts=max(nxt_h, 1),
            from_devices=n, to_devices=max(nxt, 1))
        plane_degraded(
            f"host quarantine at {seam}: {n_hosts} -> "
            f"{max(nxt_h, 1)} hosts", seam=seam,
            from_devices=n, to_devices=max(nxt, 1))
        dout("ec", 1, f"supervisor: quarantining host domain at "
                      f"{seam}; plane {n_hosts}x{dph} -> "
                      f"{max(nxt_h, 1)}x{dph}")
        if nxt >= 2:
            planemod.activate(nxt, hosts=nxt_h)
        else:
            planemod.deactivate()
        self._cache_clear()
        self._reclaim_inflight(seam)
        return rebuild()

    def _reclaim_inflight(self, seam) -> int:
        """Run the registered journal reclaim hook (if any): replay
        the lost host's intent records onto the shrunken plane.
        Counted and flight-noted so the re-dispatch is attributable."""
        with self._lock:
            cb = self._inflight_reclaim
        if cb is None:
            return 0
        n = int(cb(seam) or 0)
        if n:
            from ..telemetry import metrics as tel
            from ..telemetry import recorder
            self._count("journal_redispatches", n)
            tel.counter("supervisor_journal_redispatches", seam=seam)
            tel.event("supervisor_journal_redispatch", seam=seam,
                      ops=n)
            recorder.note("journal_redispatch", seam=seam, ops=n)
        return n

    def _quarantine(self, seam, p, rebuild):
        from ..parallel import plane as planemod
        from ..telemetry import metrics as tel
        from ..telemetry import recorder, tracing
        if tracing.enabled():
            tracing.annotate("supervisor_quarantine",
                             self.clock.monotonic(), seam=seam,
                             from_devices=p.n_devices)
        n = p.n_devices
        with self._lock:
            if self._plane_width0 is None:
                self._plane_width0 = n
            if self._plane_hosts0 is None and p.hosts > 1:
                self._plane_hosts0 = p.hosts
        nxt = n // 2
        self._count("quarantines")
        tel.counter("supervisor_quarantines", seam=seam)
        tel.event("supervisor_quarantine", seam=seam, from_devices=n,
                  to_devices=nxt)
        recorder.trip(
            "device_quarantined",
            f"mesh-member dispatch failure at {seam}: plane reshrink "
            f"{n} -> {max(nxt, 1)}",
            seam=seam, from_devices=n, to_devices=max(nxt, 1))
        plane_degraded(
            f"mesh-member quarantine at {seam}: {n} -> "
            f"{max(nxt, 1)} devices", seam=seam,
            from_devices=n, to_devices=max(nxt, 1))
        dout("ec", 1, f"supervisor: quarantining mesh member at "
                      f"{seam}; plane {n} -> {max(nxt, 1)}")
        if nxt >= 2:
            # keep the host partition when it still divides the
            # shrunken width; a non-dividing width collapses to one
            # domain (the device ladder inside the survivor)
            h = p.hosts if nxt % p.hosts == 0 else 1
            planemod.activate(nxt, hosts=h)
        else:
            planemod.deactivate()
        self._cache_clear()
        return rebuild()

    def _demote_tier(self, seam, err, cur_fn, *, rebuild, host_fn):
        from ..telemetry import metrics as tel
        from ..telemetry import recorder
        pol = self._policy()
        cur = pol.engine()
        if cur == "numpy":
            # already at the floor (no backend initialized at all, or
            # a previous demotion): the ground-truth twin completes
            # the dispatch; with no twin there is nothing left
            if host_fn is not None:
                return _HOST
            raise err
        to = pol.demote()
        with self._lock:
            self._tier_demotions += 1
            if to == "numpy":
                self._floor = "numpy"
        from ..telemetry import tracing
        if tracing.enabled():
            tracing.annotate("supervisor_demote",
                             self.clock.monotonic(), seam=seam,
                             frm=cur, to=to,
                             error=type(err).__name__)
        self._count("demotions")
        tel.counter("supervisor_demotions", seam=seam, to=to)
        tel.event("supervisor_demote", seam=seam, frm=cur, to=to,
                  error=f"{type(err).__name__}: {err}")
        recorder.trip(
            "backend_demoted",
            f"persistent dispatch failure at {seam}: live demotion "
            f"{cur} -> {to} ({type(err).__name__}: {err})",
            seam=seam, frm=cur, to=to)
        self._cache_clear()
        if to == "numpy":
            if host_fn is not None:
                return _HOST
            raise err
        return rebuild() if rebuild is not None else cur_fn

    def _host_complete(self, seam, host_fn, args):
        from ..telemetry import metrics as tel
        from .fallback import numpy_tier
        self._count("host_completions")
        tel.counter("supervisor_host_completions", seam=seam)
        with numpy_tier():
            return host_fn(*args)

    # -- self-verify -----------------------------------------------------

    def _maybe_self_verify(self, seam, out, args, *, host_fn, rebuild,
                           fn):
        if (not self.self_verify or host_fn is None
                or self._floor == "numpy"):
            return out
        parts = out if isinstance(out, (tuple, list)) else (out,)
        if not all(hasattr(p, "dtype") for p in parts):
            # only array outputs have CRC-comparable bytes; seams
            # that return host bookkeeping objects are not verifiable
            return out
        with self._lock:
            self._verify_seq += 1
            seq = self._verify_seq
        if seq % self.verify_every:
            return out
        from ..telemetry import metrics as tel
        from ..telemetry import recorder
        from .fallback import numpy_tier
        with numpy_tier():
            truth = host_fn(*args)
        if _crc_output(out) == _crc_output(truth):
            self._count("verified_clean")
            return out
        # corrupted output: flight-record, reclassify as a backend
        # fault (demote / quarantine), redispatch on the demoted tier
        # — and NEVER return the corrupted bytes
        self._count("verify_failures")
        tel.counter("supervisor_verify_failures", seam=seam)
        tel.event("supervisor_verify_failure", seam=seam)
        recorder.trip(
            "output_corruption",
            f"self-verify CRC mismatch at {seam}: device output "
            f"differs from the numpy ground truth",
            seam=seam)
        dout("ec", 1, f"supervisor: self-verify CRC mismatch at "
                      f"{seam}; reclassifying as backend fault")
        err = RuntimeError(
            f"self-verify CRC mismatch at seam {seam!r}")
        try:
            verdict = self._escalate(seam, err, fn, rebuild=rebuild,
                                     host_fn=host_fn)
        except RuntimeError:
            return truth        # ladder exhausted: ground truth wins
        if verdict is _HOST:
            self._count("host_completions")
            return truth
        redone = verdict(*args)
        if _crc_output(redone) == _crc_output(truth):
            return redone
        return truth            # still corrupt: ground truth, always

    # -- health probe / re-promotion -------------------------------------

    def _after_dispatch(self) -> None:
        if not self.demoted:
            return
        with self._lock:
            self._since_probe += 1
            fire = self._since_probe >= self.probe_every
            if fire:
                self._since_probe = 0
        if fire:
            self.tick()

    def _probe_ok(self) -> bool:
        from ..chaos.dispatch import active_plan
        from ..chaos.hosts import active_host_plan
        plan = active_plan()
        if plan is not None and plan.pending_persistent():
            return False
        hplan = active_host_plan()
        if hplan is not None and hplan.pending_persistent():
            # the adversary still holds a host down: a probe of the
            # lost domain cannot answer, however healthy the shrunken
            # plane looks — re-admission waits for the release
            return False
        if self._tier_demotions and self._policy_override is None:
            # re-probe the real backend identity without touching the
            # demotion stack: a live probe failing means still down
            try:
                import jax
                jax.default_backend()
            except (RuntimeError, ImportError):
                return False
        return True

    def tick(self) -> bool:
        """One health-probe step (the scenario loop calls this every
        turn; supervised dispatches call it every ``probe_every``
        completions).  Returns True when a re-promotion happened."""
        from ..telemetry import metrics as tel
        if not self.demoted:
            return False
        if self._probe_ok():
            with self._lock:
                self._clean_probes += 1
                promote = self._clean_probes >= self.promote_after
            self._count("probe_clean")
            tel.counter("supervisor_probe_clean")
            if promote:
                self._repromote()
                return True
        else:
            with self._lock:
                self._clean_probes = 0
            self._count("probe_failed")
            tel.counter("supervisor_probe_failed")
        return False

    def _repromote(self) -> None:
        from ..telemetry import metrics as tel
        from ..telemetry import recorder
        pol = self._policy()
        # claim the demotion state atomically, then act on the local
        # copy: pol.promote()/plane activate take their own locks and
        # must not run under ours (lockmodel rank discipline)
        with self._lock:
            n_demotions = self._tier_demotions
            self._tier_demotions = 0
            width0, self._plane_width0 = self._plane_width0, None
            hosts0, self._plane_hosts0 = self._plane_hosts0, None
            self._floor = None
            self._clean_probes = 0
        restored = None
        for _ in range(n_demotions):
            restored = pol.promote()
        if width0 is not None and self._plane_ctl:
            from ..parallel import plane as planemod
            # the recovered host re-joins: full width AND the original
            # host partition come back together
            planemod.activate(width0, hosts=hosts0 or 1)
        self._cache_clear()
        from ..telemetry import tracing
        if tracing.enabled():
            tracing.annotate("supervisor_repromote",
                             self.clock.monotonic(),
                             tier=restored or "",
                             plane_width=width0 or 0,
                             plane_hosts=hosts0 or 0)
        self._count("repromotions")
        tel.counter("supervisor_repromotions")
        if hosts0 and hosts0 > 1:
            self._count("host_repromotions")
            tel.counter("supervisor_host_repromotions")
        tel.event("supervisor_repromote", tier=restored,
                  plane_width=width0, plane_hosts=hosts0)
        recorder.trip(
            "repromoted",
            f"health probe clean x{self.promote_after}: tier restored "
            f"to {restored or 'probed'}"
            + (f", plane restored to {width0} devices"
               if width0 else "")
            + (f" across {hosts0} hosts" if hosts0 else ""),
            tier=restored or "", plane_width=width0 or 0,
            plane_hosts=hosts0 or 0)
        dout("ec", 1, f"supervisor: re-promoted (tier={restored}, "
                      f"plane={width0}, hosts={hosts0})")


# ----------------------------------------------------------------------
# shared degrade bookkeeping (ISSUE 17 satellite): ONE emission shape
# for every path that narrows the data plane — activation-time degrade
# (parallel/plane.py::_degrade), mid-run device quarantine and host
# quarantine all land here, so dashboards and the flight ring see the
# same counter/event/note regardless of WHEN the plane narrowed.

def plane_degraded(reason: str, *, seam: str = "parallel.plane",
                   from_devices: Optional[int] = None,
                   to_devices: int = 1) -> None:
    """Record one plane-narrowing event: ``engine_mesh_degraded``
    counter + structured event + flight-ring note.

    Deliberately module-level and LOCK-FREE on the supervisor side
    (telemetry locks only, ranks 300+): ``parallel.plane`` calls this
    while holding ``parallel.plane._lock`` (rank 240), and routing
    through the rank-120 ``global_supervisor()`` singleton lock there
    would invert the declared lock order (analysis/lockmodel.py)."""
    from ..telemetry import metrics as tel
    from ..telemetry import recorder
    tel.counter("engine_mesh_degraded")
    tel.event("engine_mesh_degraded", reason=reason, seam=seam,
              from_devices=from_devices, to_devices=to_devices)
    recorder.note("engine_mesh_degraded", reason=reason, seam=seam,
                  from_devices=from_devices, to_devices=to_devices)


# ----------------------------------------------------------------------
# the process supervisor

_global: Optional[DispatchSupervisor] = None
_global_lock = make_lock("ops.supervisor._global_lock")


def global_supervisor() -> DispatchSupervisor:
    global _global
    with _global_lock:
        if _global is None:
            _global = DispatchSupervisor()
        return _global


def set_global_supervisor(sup: Optional[DispatchSupervisor]
                          ) -> Optional[DispatchSupervisor]:
    """Swap the process supervisor (tests); returns the previous one."""
    global _global
    with _global_lock:
        prev = _global
        _global = sup
        return prev


def supervised(seam: str, fn: Callable, args: Tuple, *,
               host_fn: Optional[Callable] = None,
               rebuild: Optional[Callable] = None,
               splittable: bool = True):
    """The seam-side entry: route one eager dispatch through the
    process supervisor.  (Traced calls must NOT come here — the seams
    gate on tracer-ness, so jitted programs stay supervision-free.)"""
    return global_supervisor().dispatch(
        seam, fn, args, host_fn=host_fn, rebuild=rebuild,
        splittable=splittable)


# ----------------------------------------------------------------------
# the tpu-audit host-tier workload

def supervisor_selftest() -> dict:
    """The ``ops.supervisor`` host-tier audit entry: the full
    classification ladder — transient retry, OOM split, persistent
    backend loss with live demotion to the ground-truth twin,
    corrupt-output self-verify, health-probe re-promotion — on
    ISOLATED state (own FakeClock, own FallbackPolicy, own fault
    plan, no pattern cache, no plane): ZERO jax compiles, zero device
    arrays, forever.  The supervisor is host control flow by
    construction — a recovery plane that itself needed the device
    would deadlock exactly when it matters."""
    from ..chaos.dispatch import (DispatchFault, DispatchFaultPlan,
                                  arm_plan)
    from ..utils.retry import FakeClock
    from .fallback import FallbackPolicy

    pol = FallbackPolicy(force="xla")
    sup = DispatchSupervisor(
        clock=FakeClock(), policy=pol, cache_clear=lambda: None,
        plane_ctl=False, self_verify=True, promote_after=2,
        probe_every=1)
    data = np.arange(64, dtype=np.uint8).reshape(4, 16)

    def body(x):
        return x ^ np.uint8(0xA5)

    plan = DispatchFaultPlan([
        DispatchFault("transient", seam="selftest.seam", at=2,
                      calls=1),
        DispatchFault("oom", seam="selftest.seam", at=4, calls=1),
        DispatchFault("corrupt", seam="selftest.seam", at=7, calls=1),
        DispatchFault("backend_loss", seam="selftest.seam", at=9,
                      calls=3),
    ], seed=7)
    prev = arm_plan(plan)
    try:
        want = body(data)
        for _ in range(8):
            got = sup.dispatch("selftest.seam", body, (data,),
                               host_fn=body)
            if _crc_output(got) != _crc_output(want):
                raise AssertionError("supervised output diverged")
        st = sup.stats()
        if not (st["retries"] >= 1 and st["rung_downshifts"] >= 1
                and st["verify_failures"] >= 1):
            raise AssertionError(f"ladder not exercised: {st}")
        if st["demotions"] < 1 or not st["demoted"]:
            raise AssertionError(f"no demotion recorded: {st}")
        plan.clear()
        for _ in range(4):
            got = sup.dispatch("selftest.seam", body, (data,),
                               host_fn=body)
            if _crc_output(got) != _crc_output(want):
                raise AssertionError("post-heal output diverged")
        if not sup.stats()["repromotions"]:
            sup.tick()
        st = sup.stats()
        if not st["repromotions"] or st["demoted"]:
            raise AssertionError(f"re-promotion never happened: {st}")
        if pol.engine() != "xla":
            raise AssertionError("policy tier not restored")
    finally:
        arm_plan(prev)
    return sup.stats()


__all__ = ["DispatchSupervisor", "classify_dispatch_error",
           "global_supervisor", "plane_degraded",
           "set_global_supervisor", "supervised",
           "supervisor_selftest"]
