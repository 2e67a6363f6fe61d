"""ceph_tpu.serve — the ragged continuous-batching serving front-end
(docs/SERVING.md; ROADMAP item 3).

Everything below this package is a batch library: hand it a
pre-stacked ``(B, k, C)`` array and it runs one program.  Production
traffic — "heavy traffic from millions of users" — is a *stream* of
mixed (plugin, k, m, stripe-size, op) requests with deadlines.  This
package is the conversion layer:

- ``queue``   — :class:`EcRequest` + the bounded, clock-injectable
                admission queue (reject-at-the-door overload policy).
- ``batcher`` — the continuous batcher: shape buckets keyed exactly
                like the PatternCache, batch dim padded up a small
                fixed rung ladder, deadline-slack firing; zero warm
                recompiles by construction.  Mixed stripe sizes
                are served by one bucket per chunk size.
- ``sla``     — per-op-class SLO policy + evaluation (p50/p99/p999,
                GB/s-under-SLO, deadline-miss and padding overheads).
- ``loadgen`` — seeded open/closed-loop traffic generation and the
                shared scenario driver (bench ``--workload serving``,
                tools/serve_demo.py, tests).

Host bookkeeping never imports jax at module scope; the device seam
is :func:`ceph_tpu.codes.engine.serve_dispatch_call`, audited as the
``serve.dispatch`` jit-tier entry (the ``serve.batcher`` host-tier
entry pins the bookkeeping compile-free).
"""

from .queue import OPS, AdmissionQueue, EcRequest, EcResult
from .sla import BurnRateMonitor, SlaRecorder, SloPolicy
from .batcher import LADDER, ContinuousBatcher, rung_for
from .loadgen import (
    CodecSpec,
    LoadGenerator,
    ServingRun,
    TrafficSpec,
    default_spec,
    run_serving_scenario,
    throughput_service_model,
    verify_results,
)

__all__ = [
    "AdmissionQueue",
    "BurnRateMonitor",
    "CodecSpec",
    "ContinuousBatcher",
    "EcRequest",
    "EcResult",
    "LADDER",
    "LoadGenerator",
    "OPS",
    "ServingRun",
    "SlaRecorder",
    "SloPolicy",
    "TrafficSpec",
    "default_spec",
    "rung_for",
    "run_serving_scenario",
    "throughput_service_model",
    "verify_results",
]
