"""Explicit Pallas → XLA → numpy backend fallback policy.

Before this module, engine selection was scattered and silent: the
device probe in ``pallas_gf`` swallowed every exception with a bare
``except Exception`` and quietly answered "cpu", so a broken jax
install, a failed device init, or a typo'd platform string all looked like
a deliberate CPU run.  The policy object makes the three-tier ladder
(SURVEY §2.3: Pallas kernels on TPU → XLA SWAR everywhere else →
numpy ground truth when no XLA backend initializes) an explicit,
observable decision.  The CPU tiers are for explicit CPU runs
(``JAX_PLATFORMS=cpu``); the entry points that measure or prove the
chip (``chip_smoke.py``, ``bench.py``) require a TPU and assert that
the policy never left it:

- the probe catches only the exception types jax actually raises for
  "no usable backend" (RuntimeError from backend init, ImportError
  from a broken install) — anything else is a real bug and propagates;
- the selected engine is logged ONCE per distinct (device, engine)
  outcome through utils.log (``CEPH_TPU_DEBUG=ec=1`` shows it);
- ``CEPH_TPU_ENGINE=pallas|xla|numpy`` force-overrides for tests and
  benches, replacing ad-hoc monkeypatching of the probe.

``pallas_gf.use_pallas``, the per-matrix engine selection table
(``pallas_gf.select_matrix_engine`` — MXU/Pallas/XLA/numpy per
(shape, matrix, layout); docs/PERF.md "Unified decode/repair engine"
has the table) and the mixin host/device split in
``codes/techniques.py`` all route through ``global_policy()``.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from typing import List, Optional, Tuple

from ..utils.log import dout
from ..utils.locks import make_lock

ENGINES = ("pallas", "xla", "numpy")

_tls = threading.local()


@contextmanager
def numpy_tier():
    """Thread-local numpy-tier override: inside the block every
    ``engine()`` answer is ``"numpy"``, so the host batch surfaces
    (codes/techniques.py) run the ground-truth numpy path without
    mutating process state.  The supervised dispatch plane
    (ops/supervisor.py) computes its self-verify ground truth and its
    demoted-completion twins under this, so a verification pass can
    never itself dispatch through the backend being verified."""
    _tls.numpy = getattr(_tls, "numpy", 0) + 1
    try:
        yield
    finally:
        _tls.numpy -= 1


def _numpy_forced() -> bool:
    return getattr(_tls, "numpy", 0) > 0

# device kind reported when no XLA backend can initialize at all — the
# numpy tier (the probe error is kept for the log line)
NO_BACKEND = "none"


class FallbackPolicy:
    """Maps the probed device kind to a compute engine tier.

    tpu → pallas (Mosaic lowers there), any other live backend → xla,
    no backend at all → numpy.
    """

    def __init__(self, force: Optional[str] = None) -> None:
        env = os.environ.get("CEPH_TPU_ENGINE", "").strip().lower()
        self.force = force if force is not None else (env or None)
        if self.force is not None and self.force not in ENGINES:
            raise ValueError(
                f"engine {self.force!r} must be one of {ENGINES}")
        self.probe_error: Optional[BaseException] = None
        self._logged: set = set()
        self._lock = make_lock("ops.fallback.FallbackPolicy._lock")
        self._kind: Optional[str] = None
        # live-demotion stack (ops/supervisor.py): each demote()
        # pushes the force it replaced so promote() restores exactly
        self._demote_stack: List[Optional[str]] = []
        self.demotions = 0
        self.promotions = 0

    # -- probe -----------------------------------------------------------

    def device_kind(self) -> str:
        """The default jax backend platform, or NO_BACKEND.

        jax.default_backend() raises RuntimeError when no platform
        initializes (and ImportError surfaces a broken install); both
        mean "drop to the numpy tier".  Nothing else is swallowed.
        The probe result is cached because the hot host paths ask on
        every batch — but backend identity CAN change mid-process (a
        device loss): the supervised dispatch plane
        (ops/supervisor.py) calls :meth:`invalidate` / :meth:`demote`
        to flip the cached answer live when a dispatch seam reports a
        persistent backend failure, and :meth:`invalidate` again when
        its health probe re-promotes.
        """
        with self._lock:
            if self._kind is not None:
                return self._kind
        # the probe itself runs UNLOCKED: backend init can stall, and
        # invalidate()/demote() must stay callable
        # while it does.  First writer wins; a concurrent invalidate()
        # landing between probe and publish just costs one re-probe.
        import jax
        err: Optional[BaseException] = None
        try:
            kind = jax.default_backend()
        except (RuntimeError, ImportError) as e:
            err = e
            kind = NO_BACKEND
        with self._lock:
            if self._kind is None:
                self._kind = kind
                self.probe_error = err
            return self._kind

    def invalidate(self) -> None:
        """Drop the cached probe result (and its error): the next
        :meth:`device_kind` re-probes the backend.  The supervised
        dispatch plane calls this around live demotion/re-promotion —
        the one sanctioned way backend identity changes mid-process."""
        with self._lock:
            self._kind = None
            self.probe_error = None

    def demote(self, to: Optional[str] = None) -> str:
        """LIVE tier demotion (ops/supervisor.py): force the next tier
        down the pallas → xla → numpy ladder (or the explicit ``to``)
        and invalidate the probe cache.  Returns the new tier.  Each
        demotion pushes the force it replaced so :meth:`promote`
        restores exactly; the transition is logged + counted like any
        other tier change."""
        cur = self.engine()
        if to is None:
            idx = ENGINES.index(cur) if cur in ENGINES else 0
            to = ENGINES[min(idx + 1, len(ENGINES) - 1)]
        if to not in ENGINES:
            raise ValueError(f"demote target {to!r} must be one of "
                             f"{ENGINES}")
        with self._lock:
            self._demote_stack.append(self.force)
            self.force = to
            self.demotions += 1
        self.invalidate()
        dout("ec", 1, f"backend fallback policy: LIVE demotion "
                      f"{cur} -> {to}")
        self._log_once(f"demoted-from-{cur}", to)
        return to

    def promote(self) -> Optional[str]:
        """Undo the most recent :meth:`demote` (the health probe's
        re-promotion); returns the restored engine tier, or None when
        nothing was demoted."""
        with self._lock:
            if not self._demote_stack:
                return None
            self.force = self._demote_stack.pop()
            self.promotions += 1
        self.invalidate()
        eng = self.engine()
        dout("ec", 1, f"backend fallback policy: re-promoted to "
                      f"engine={eng}")
        return eng

    @property
    def demoted(self) -> bool:
        with self._lock:
            return bool(self._demote_stack)

    # -- selection -------------------------------------------------------

    def engine(self, device_kind: Optional[str] = None) -> str:
        """The engine tier for ``device_kind`` (probed when omitted)."""
        if _numpy_forced():
            return "numpy"
        if self.force is not None:
            kind = device_kind if device_kind is not None else "forced"
            self._log_once(kind, self.force, forced=True)
            return self.force
        if device_kind is None:
            device_kind = self.device_kind()
        if device_kind == "tpu":
            eng = "pallas"
        elif device_kind == NO_BACKEND:
            eng = "numpy"
        else:
            eng = "xla"
        self._log_once(device_kind, eng)
        return eng

    def _log_once(self, kind: str, eng: str, forced: bool = False) -> None:
        key: Tuple[str, str] = (kind, eng)
        with self._lock:
            if key in self._logged:
                return
            self._logged.add(key)
        why = "forced via CEPH_TPU_ENGINE" if forced else f"device={kind}"
        tail = (f"; probe error: {type(self.probe_error).__name__}: "
                f"{self.probe_error}" if self.probe_error else "")
        dout("ec", 1, f"backend fallback policy: engine={eng} ({why}){tail}")
        # the log-once transition is ALSO a counter + structured event
        # in the telemetry plane: a tier drop mid-fleet is a metric to
        # alert on, not just a line someone may have had enabled (the
        # event additionally lands in the flight recorder's ring)
        from ..telemetry import metrics as tel
        tel.counter("fallback_tier_transitions", device=kind, engine=eng)
        tel.event("fallback_tier", device=kind, engine=eng,
                  forced=forced,
                  probe_error=(f"{type(self.probe_error).__name__}: "
                               f"{self.probe_error}"
                               if self.probe_error else None))
        if eng == "numpy" and not forced:
            # an UNFORCED drop to the numpy ground-truth tier means no
            # XLA backend initialized at all — on a deployment that is
            # an outage, so freeze the post-mortem (the probe error is
            # exactly the evidence an operator needs)
            from ..telemetry import recorder
            recorder.trip(
                "backend_lost",
                f"fallback to numpy tier: {tail or why}",
                device=kind, engine=eng)


_global: Optional[FallbackPolicy] = None
_global_lock = make_lock("ops.fallback._global_lock")


def global_policy() -> FallbackPolicy:
    global _global
    with _global_lock:
        if _global is None:
            _global = FallbackPolicy()
        return _global


def set_global_policy(policy: Optional[FallbackPolicy]) -> \
        Optional[FallbackPolicy]:
    """Swap the process policy (tests); returns the previous one."""
    global _global
    with _global_lock:
        prev = _global
        _global = policy
        return prev
