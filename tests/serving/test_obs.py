"""Observability-plane tests (DESIGN.md §12):

  * the tracer is a pure OBSERVER: the same seeded sim serve emits
    bit-identical token streams with tracing on and off,
  * determinism: the virtual-clock trace pins a GOLDEN span digest
    (full ring, timestamps included) run-to-run and commit-to-commit,
    while the decision digest is invariant to arrival order and lane
    placement (the tracer-level mirror of (rid, token)-keyed rows),
  * ring/span bounding, request span lifecycle,
  * flight-recorder triggers: forced page exhaustion through a real
    serve (bundle carries the triggering request's full span history),
    plus SLO-burst / gear-thrash / stuck-waiter units,
  * metrics registry absorb/labels/Prometheus/JSON, the bounded
    `RuntimeMetrics.to_json`, Perfetto export structure (validated by
    the same hand-rolled checker CI runs), and decision attribution.
"""

import json

import numpy as np
import pytest

from repro import strategy
from repro.core import traces
from repro.serving import runtime as rt
from repro.serving.obs import (FlightRecorder, MetricsRegistry,
                               Observability, SpanTracer,
                               decision_attribution)
from repro.serving.obs.export import (profiler_capture, to_perfetto,
                                      write_trace)
from repro.serving.runtime.request import Request
from repro.serving.runtime.workload import WorkloadSpec, make_workload

N_NODES = 5

# The golden full-ring digest of the seeded sim serve below — same
# idiom as the strategy goldens: any change to event schema, ordering,
# timestamps, or decisions shows up here first and must be intentional
# (recompute with `_traced_serve(...)[1].tracer.span_digest()`).
# Last recompute: token events grew the ``deepest`` probed-node tag
# (the regret meter's recall-forgone attribution input, §15).
GOLDEN_SPAN_DIGEST = \
    "0359a77e7d911ca1da679fef18393ddf3d14a950eb0ed60c4cb2a542f47650aa"


@pytest.fixture(scope="module")
def sim_cascade():
    rng = np.random.default_rng(0)
    losses, _, flops = traces.ee_like_traces(rng, 3_000, N_NODES)
    casc = strategy.Cascade.from_traces(losses[:1_500], 0.4 * flops,
                                        k=12, lam=0.6)
    return casc, losses[1_500:]


def _workload():
    spec = WorkloadSpec(rate=4.0, duration=10.0, prompt_len=4,
                        max_tokens=(2, 9), seed=11)
    return make_workload("poisson", spec)


def _traced_serve(casc, bank, requests, *, lanes=3, obs="tracer",
                  stepper_cls=rt.SimStepper, slo=5.0, **stepper_kw):
    strategies, sid_of = rt.build_bank(requests, rt.cascade_factory(casc),
                                       ("recall_index", None))
    stepper = stepper_cls(strategies, bank, n_lanes=lanes,
                          seg_time=0.05, overhead=0.01, **stepper_kw)
    if obs == "tracer":
        obs = Observability()
    server = rt.Server(stepper, rt.LaneScheduler(lanes), sid_of,
                       slo=slo, obs=obs)
    return server.serve(requests), obs


# --------------------------------------------------------------------------
# tracing is a pure observer; the trace itself is deterministic
# --------------------------------------------------------------------------

def test_tracing_on_off_identical_streams(sim_cascade):
    casc, bank = sim_cascade
    requests = _workload()
    m_off, _ = _traced_serve(casc, bank, requests, obs=None)
    m_on, obs = _traced_serve(casc, bank, requests)
    assert set(m_on.records) == set(m_off.records)
    for rid in m_off.records:
        assert m_on.records[rid].tokens == m_off.records[rid].tokens, rid
    assert obs.tracer.n_emitted > 0


def test_span_digest_golden_and_reproducible(sim_cascade):
    casc, bank = sim_cascade
    requests = _workload()
    _, obs1 = _traced_serve(casc, bank, requests)
    _, obs2 = _traced_serve(casc, bank, requests)
    assert obs1.tracer.span_digest() == obs2.tracer.span_digest()
    assert obs1.tracer.span_digest() == GOLDEN_SPAN_DIGEST
    assert obs1.tracer.dropped == 0


def test_decision_digest_arrival_order_invariant(sim_cascade):
    """Reversed arrivals re-order lanes and timestamps, but the
    per-request served-node streams — hence the decision digest —
    cannot move (the (rid, token)-keyed row property, observed at the
    tracer level)."""
    casc, bank = sim_cascade
    base = [Request(rid=rid, prompt=np.zeros(4, np.int32),
                    max_tokens=3 + rid % 5, arrival=0.0)
            for rid in range(8)]
    staggered = [Request(rid=r.rid, prompt=r.prompt,
                         max_tokens=r.max_tokens,
                         arrival=float((7 - r.rid) * 0.3))
                 for r in base]
    _, obs1 = _traced_serve(casc, bank, base, lanes=2)
    _, obs2 = _traced_serve(casc, bank, staggered, lanes=2)
    assert obs1.tracer.decision_digest() == obs2.tracer.decision_digest()


def test_request_span_lifecycle(sim_cascade):
    casc, bank = sim_cascade
    requests = _workload()
    metrics, obs = _traced_serve(casc, bank, requests)
    rid = requests[0].rid
    span = obs.tracer.request_span(rid)
    kinds = [ev.kind for ev in span]
    assert kinds[0] == "queued" and kinds[1] == "admitted"
    assert kinds[-1] == "finish"
    tokens = [ev for ev in span if ev.kind == "token"]
    assert len(tokens) == metrics.records[rid].n_tokens
    # first token carries the ttft the flight recorder watches; every
    # token carries the served-loss the attribution rows sum
    first = dict(tokens[0].data)
    assert first.get("ttft") == pytest.approx(metrics.records[rid].ttft)
    assert all("loss" in dict(ev.data) for ev in tokens)
    # timestamps are the virtual clock: non-decreasing within the span
    ts = [ev.t for ev in span]
    assert ts == sorted(ts)


def test_tracer_ring_and_span_bounds():
    tr = SpanTracer(capacity=8, span_events=3, keep_finished=1)
    for i in range(20):
        tr.emit("token", t=float(i), rid=7, lane=0, node=1, sid=0)
    assert len(tr.events) == 8 and tr.dropped == 12
    assert len(tr.request_span(7)) == 3       # span cap, overflow counted
    assert tr.span_dropped(7) == 17
    tr.emit("finish", t=21.0, rid=7)
    tr.emit("queued", t=22.0, rid=8)
    tr.emit("finish", t=23.0, rid=8)          # retires 8, evicts 7
    assert tr.request_span(8) and not tr.request_span(7)
    s = tr.stats()
    assert s["emitted"] == 23 and s["finished_spans"] == 1


# --------------------------------------------------------------------------
# flight recorder
# --------------------------------------------------------------------------

class _GatedSimStepper(rt.SimStepper):
    """SimStepper with a scripted admission gate: refuses the first
    ``blocks`` reservation attempts of each rid in ``block_rids`` —
    the deterministic page-exhaustion forcing for the flight test
    (the real `KVPool.reserve` path is covered by test_kvpool)."""

    block_rids: tuple = ()
    blocks: int = 0

    def alloc(self):
        super().alloc()
        self._denied = {rid: self.blocks for rid in self.block_rids}

    def reserve(self, req):
        left = self._denied.get(req.rid, 0)
        if left > 0:
            self._denied[req.rid] = left - 1
            return False
        return True


def test_flight_page_exhaustion_dumps_bundle(sim_cascade, tmp_path):
    """The acceptance scenario: forced page exhaustion fires a
    flight-recorder bundle that carries the triggering request's full
    span history."""
    casc, bank = sim_cascade

    class Gated(_GatedSimStepper):
        block_rids = (1,)
        blocks = 4

    requests = [
        Request(rid=0, prompt=np.zeros(4, np.int32), max_tokens=9,
                arrival=0.0),
        Request(rid=1, prompt=np.zeros(4, np.int32), max_tokens=3,
                arrival=0.0),
    ]
    flight = FlightRecorder(out_dir=str(tmp_path), page_burst=3)
    obs = Observability(flight=flight)
    # two lanes: rid 0 keeps one busy while rid 1's reservations are
    # refused — the pool-stopped-turning-over streak, not a dead server
    metrics, _ = _traced_serve(casc, bank, requests, lanes=2, obs=obs,
                               stepper_cls=Gated)
    # the serve still completes — blocked admission queues, not drops
    assert all(metrics.records[r.rid].finished is not None
               for r in requests)
    assert [b["trigger"] for b in flight.bundles] == ["page_exhaustion"]
    bundle = flight.bundles[0]
    assert bundle["rid"] == 1 and bundle["detail"]["streak"] == 3
    span_kinds = [ev["kind"] for ev in bundle["request_span"]]
    assert span_kinds[0] == "queued"
    assert span_kinds.count("page_blocked") >= 3
    # the metrics snapshot is frozen AT trigger time: rid 1 was still
    # refused admission, so only rid 0 had been admitted
    assert bundle["metrics"]["requests"] == 1
    # the bundle also landed on disk, schema-tagged
    [path] = flight.dump_paths
    with open(path) as f:
        on_disk = json.load(f)
    assert on_disk["schema"] == "flight_bundle/v1"
    assert on_disk["trigger"] == "page_exhaustion"
    assert flight.stats()["triggers"] == {"page_exhaustion": 1}


def _bound_pair(**kw):
    tr = SpanTracer()
    fl = FlightRecorder(**kw)
    fl.bind(tr)
    return tr, fl


def test_flight_slo_burst_trigger_and_cap():
    tr, fl = _bound_pair(slo=0.1, slo_burst=3, max_bundles_per_kind=1)
    for i in range(3):
        tr.emit("token", t=float(i), rid=i, ttft=0.5, node=0, sid=0)
    assert [b["trigger"] for b in fl.bundles] == ["slo_burst"]
    assert fl.bundles[0]["detail"]["streak"] == 3
    # an in-SLO first token resets the streak; the cap stops a storm
    tr.emit("token", t=3.0, rid=9, ttft=0.01, node=0, sid=0)
    assert fl._slo_streak == 0
    for i in range(6):
        tr.emit("token", t=4.0 + i, rid=i, ttft=0.5, node=0, sid=0)
    assert len(fl.bundles) == 1


def test_flight_gear_thrash_trigger():
    tr, fl = _bound_pair(thrash_count=3, thrash_window=10.0)
    tr.emit("gear_switch", t=0.0, src=0, dst=1)
    tr.emit("gear_switch", t=20.0, src=1, dst=0)   # outside the window
    tr.emit("gear_switch", t=21.0, src=0, dst=1)
    assert not fl.bundles
    tr.emit("gear_switch", t=22.0, src=1, dst=0)
    assert [b["trigger"] for b in fl.bundles] == ["gear_thrash"]
    assert fl.bundles[0]["detail"]["switches"] == 3


def test_flight_stuck_waiter_trigger_and_grant_clears():
    tr, fl = _bound_pair(stuck_after=5.0)
    tr.emit("esc_wait", t=0.0, rid=3, model=1)
    tr.emit("esc_grant", t=1.0, rid=3, model=1, lane=0)   # clears
    tr.emit("counter", t=10.0, queue=0)
    assert not fl.bundles
    tr.emit("esc_wait", t=10.0, rid=4, model=1)
    tr.emit("counter", t=16.0, queue=0)   # any event's clock ages waiters
    assert [b["trigger"] for b in fl.bundles] == ["stuck_waiter"]
    assert fl.bundles[0]["rid"] == 4
    assert fl.bundles[0]["detail"]["waited_s"] == pytest.approx(6.0)


# --------------------------------------------------------------------------
# metrics registry + bounded runtime records
# --------------------------------------------------------------------------

def test_registry_absorb_labels_and_prometheus(tmp_path):
    reg = MetricsRegistry()
    reg.absorb("runtime", {"tokens": 41, "ttft": {"p50": 0.018},
                           "note": "skipped", "flag": True,
                           "hist": [1, 2, 3]})
    reg.absorb("kv_pool", {"pages_peak": 9}, model="small")
    reg.counter("serve_errors").inc()
    reg.histogram("step_seconds").observe(0.004)
    snap = reg.snapshot()
    assert snap["runtime_tokens"] == 41.0
    assert snap["runtime_ttft_p50"] == pytest.approx(0.018)
    assert snap["runtime_flag"] == 1.0
    assert snap["runtime_hist_1"] == 2.0
    assert snap['kv_pool_pages_peak{model="small"}'] == 9.0
    assert "runtime_note" not in snap        # strings are not series
    assert reg.value("kv_pool_pages_peak", model="small") == 9.0
    assert reg.value("missing", default=-1.0) == -1.0
    text = reg.prometheus_text()
    assert '# TYPE serve_errors counter' in text
    assert 'kv_pool_pages_peak{model="small"} 9' in text
    assert 'step_seconds_bucket{le="+Inf"} 1' in text
    # the snapshot JSON passes the same validator CI runs on artifacts
    from benchmarks.check_trace import validate_metrics
    doc = reg.to_json(str(tmp_path / "m.json"), extra={"leg": "unit"})
    assert validate_metrics(doc) == []
    assert validate_metrics(json.load(open(tmp_path / "m.json"))) == []


def test_metrics_to_json_bounds_records(tmp_path):
    from repro.serving.runtime.metrics import RuntimeMetrics
    m = RuntimeMetrics(full_depth=4, n_lanes=2)
    m.t_start, m.t_end = 0.0, 10.0
    for rid in range(10):
        req = Request(rid=rid, prompt=np.zeros(2, np.int32), max_tokens=1,
                      arrival=float(rid))
        m.on_admit(req, float(rid))
        m.on_token(rid, served_node=1, now=rid + 0.5, token=1)
        m.on_finish(rid, rid + 0.5)
    path = tmp_path / "r.json"
    doc = m.to_json(str(path), slo=1.0, max_records=4)
    assert len(doc["requests"]) == 4
    assert doc["requests_dropped"] == 6
    # newest arrivals survive, oldest are the ones dropped
    assert sorted(r["rid"] for r in doc["requests"]) == [6, 7, 8, 9]
    full = m.to_json(str(path), slo=1.0, max_records=None)
    assert len(full["requests"]) == 10 and full["requests_dropped"] == 0


# --------------------------------------------------------------------------
# export + attribution
# --------------------------------------------------------------------------

def test_perfetto_export_structure_and_validator(sim_cascade, tmp_path):
    casc, bank = sim_cascade
    requests = _workload()
    metrics, obs = _traced_serve(casc, bank, requests)
    path = tmp_path / "trace.json"
    doc = write_trace(obs.tracer, str(path), title="unit serve")
    from benchmarks.check_trace import validate_trace
    assert validate_trace(doc) == []
    assert validate_trace(json.load(open(path))) == []
    phases = {}
    for ev in doc["traceEvents"]:
        phases[ev["ph"]] = phases.get(ev["ph"], 0) + 1
    # one X request span per completed request, on the lanes process
    spans = [ev for ev in doc["traceEvents"] if ev["ph"] == "X"]
    assert len(spans) == len(requests)
    assert all(ev["pid"] == 0 and ev["dur"] >= 0 for ev in spans)
    assert phases["C"] > 0 and phases["i"] > 0    # counters + decisions
    assert doc["otherData"]["events_dropped"] == 0


def test_perfetto_open_span_for_unfinished_request():
    tr = SpanTracer()
    tr.emit("admitted", t=1.0, rid=5, lane=2, sid=0)
    tr.emit("token", t=2.0, rid=5, lane=2, node=1, sid=0)
    doc = to_perfetto(tr.events)
    [span] = [ev for ev in doc["traceEvents"] if ev["ph"] == "X"]
    assert span["name"] == "req 5 (open)" and span["args"]["open"]
    assert span["ts"] == 1e6 and span["dur"] == 1e6


def test_decision_attribution_accounts_every_token(sim_cascade):
    casc, bank = sim_cascade
    requests = _workload()
    metrics, obs = _traced_serve(casc, bank, requests)
    rows = decision_attribution(obs.tracer.events,
                                gear_of=lambda sid: f"gear{sid}")
    assert sum(r["tokens"] for r in rows) == \
        sum(rec.n_tokens for rec in metrics.records.values())
    assert all(r["gear"] == "gear0" for r in rows)
    assert all(r["latency_sum_s"] >= 0.0 for r in rows)
    assert all(r["served_loss_mean"] is not None for r in rows)
    # exit nodes cover more than one depth, else attribution is moot
    assert len({r["node"] for r in rows}) > 1


# --------------------------------------------------------------------------
# report rendering (the serve.py dedupe)
# --------------------------------------------------------------------------

def test_serve_report_renders_from_registry():
    from repro.serving.obs.report import ServeReport
    rep = ServeReport()
    rep.add_runtime({"completed": 3, "requests": 4, "tokens": 41,
                     "duration": 1.5, "throughput_tok_s": 27.3,
                     "throughput_req_s": 2.0,
                     "ttft": {"p50": 0.018, "p95": 0.03, "p99": 0.04},
                     "token_latency": {"p50": 0.004, "p95": 0.01,
                                       "p99": 0.014},
                     "goodput_tok_s": 27.3, "slo_attainment": 1.0},
                    slo_ms=1000.0)
    rep.add_pool({"pages_peak": 9, "n_pages": 13, "prefix_hit_rate": 0.5,
                  "shared_tokens": 12, "cow_splits": 1, "evictions": 0,
                  "grows": 0, "reserve_failures": 2})
    lines = rep.lines()
    assert lines[0] == "completed 3/4 requests, 41 tokens in 1.50s"
    assert any(l.startswith("goodput (ttft<=1000ms): 27.3 tok/s")
               for l in lines)
    [pool_line] = [l for l in lines if l.startswith("kv pool:")]
    assert "peak 9/12 pages" in pool_line
    assert "2 blocked admissions" in pool_line
    # the console report and the snapshot read the same registry
    assert rep.registry.value("kv_pool_reserve_failures") == 2.0


@pytest.mark.parametrize("fails", ["start_trace", "stop_trace"])
def test_profiler_capture_failure_propagates(monkeypatch, tmp_path, fails):
    """A run asked for a profiler trace must not finish quietly without
    one: an error from either end of the capture reaches the caller."""
    import jax

    def boom(*a, **k):
        raise RuntimeError(f"{fails} failed")

    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, fails, boom)
    with pytest.raises(RuntimeError, match=fails):
        with profiler_capture(str(tmp_path)):
            pass
    with profiler_capture(None):    # no logdir: the profiler is untouched
        pass
