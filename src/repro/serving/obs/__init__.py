"""Unified serving observability plane (DESIGN.md §12).

One package threads through every serving subsystem:

  * `trace`    — `SpanTracer`: bounded host-side ring of lifecycle
    events (queued → admitted → prefill chunks → per-token decode →
    escalate/recall/de-escalate → finish), fed only from data the
    steppers already sync once per token, and opt-in: every producer
    guards with ``if tracer is not None``.  Its second ring holds
    interval spans at the serving path's layer boundaries, of step and
    request granularity and always on (nothing turns them off):
    ``server.iteration``, ``request.queue``, ``admission.blocked``,
    ``engine.admit``, ``engine.step`` (children ``engine.plan``,
    ``engine.page_ops``, ``engine.dispatch``, ``engine.sync``, with the
    step's upload and segment counts and its public `StepRecord`) and
    ``pool.prepare_step`` / ``admit`` / ``release`` /
    ``commit_prefix``.  `Server.serve` starts a session on the process
    tracer `TRACER` (on ``obs.tracer`` when it has an `Observability`),
    which clears the span ring: it holds the last serve until the next
    starts.  Each live span is mirrored into an active
    ``jax.profiler`` trace as a ``TraceAnnotation`` carrying its
    ``span_id``.  No annotation covers a whole serve or window (a
    trace reader names each idle gap by the innermost span covering
    it, so one would name them all), and no device scope
    (``jax.named_scope``) names a kernel (the trace reduction would
    count the scope's operations as that kernel): the pool's in-place
    row writes are ``page_write``, not ``paged_attention``.
  * `registry` — `MetricsRegistry`: counters/gauges/histograms with
    labels, absorbing the per-subsystem stats dicts behind one
    ``snapshot()`` / Prometheus-text / JSON surface.
  * `export`   — Chrome/Perfetto trace-event JSON (one track per
    lane, one per model rung, decision instants) + an optional
    ``jax.profiler`` capture around a serve (`profiler_capture`).
  * `flight`   — `FlightRecorder`: last-N-events post-mortem bundles
    on anomaly triggers (TTFT-SLO breach burst, page exhaustion,
    stuck escalation waiter, gear thrash).
  * `audit`    — `InvariantLedger`: streaming contracts over the same
    listener hook (page conservation, escalations resolve, lane
    occupancy, walk-floor monotonicity, TTFT-exactly-once, admission
    never drops) with flight-bundle dumps on violation.
  * `replay`   — deterministic re-serve of an exported trace artifact
    with `span_digest` / `decision_digest` equality checks.
  * `lossmap`  — goodput-loss attribution: the achieved-vs-roofline
    gap decomposed into causes from span intervals.
  * `regret`   — `RegretMeter`: per-request distance from the
    offline-optimal walk (the paper's separation theorem as live
    telemetry), decomposed by decision cause, as a pure listener.
  * `pareto`   — `ParetoTracker`: the streaming empirical
    accuracy-latency frontier with per-gear attribution.
  * `report`   — the one serve report renderer (replaces the bespoke
    print blocks `launch/serve.py` used to duplicate).

`Observability` is the small bundle the `Server` accepts.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from repro.serving.obs.audit import InvariantLedger, audit_events
from repro.serving.obs.flight import FlightRecorder
from repro.serving.obs.pareto import ParetoTracker
from repro.serving.obs.regret import RegretMeter, regret_events
from repro.serving.obs.registry import MetricsRegistry
from repro.serving.obs.trace import (TRACER, Span, SpanTracer, StepRecord,
                                     decision_attribution)

__all__ = [
    "FlightRecorder",
    "InvariantLedger",
    "MetricsRegistry",
    "Observability",
    "ParetoTracker",
    "RegretMeter",
    "Span",
    "SpanTracer",
    "StepRecord",
    "TRACER",
    "audit_events",
    "decision_attribution",
    "regret_events",
]


@dataclass
class Observability:
    """What a `Server` threads through a serve: a tracer (always, when
    observability is on; it then takes the serve's spans too), and an
    optional flight recorder, invariant ledger and regret meter riding
    the same event stream."""

    tracer: SpanTracer = field(default_factory=SpanTracer)
    flight: FlightRecorder | None = None
    ledger: InvariantLedger | None = None
    regret: RegretMeter | None = None
