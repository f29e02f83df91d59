"""Interval spans of the serving path (DESIGN.md §12):

  * spans nest with the right parents, rids and counts; a backend
    compile lands on the innermost open span; past-start spans are kept
    in memory only;
  * every serve records its spans into the process tracer, whose ring
    holds the last serve until the next starts, also when the serve
    ends by an exception; admission waits are split by cause;
  * the spans mirrored into a profiler trace join the in-memory ones by
    ``span_id`` at one clock offset;
  * on the real engine, spans change no served token, and a serve with
    an `Observability` keeps them on its own tracer;
  * the step program's device scopes are named, and none names a
    kernel.
"""

import collections
import glob
import re

import numpy as np
import pytest

from repro.serving import runtime as rt
from repro.serving.kvpool import KVPool
from repro.serving.obs import Observability, SpanTracer
from repro.serving.obs.trace import TRACER
from repro.serving.runtime.request import Request
from repro.strategy.line import FixedNodeStrategy

jax = pytest.importorskip("jax")

N_NODES = 3


def test_spans_nest_with_parents_rids_and_counts():
    tr = SpanTracer()
    tr.begin_session(lambda: 0.0)
    with tr.span("outer", rid=4) as outer:
        with tr.span("inner", lane=2, uploads=3) as inner:
            inner.add(upload_bytes=12)
        with tr.span("inner") as second:
            pass
        past = tr.record("request.queue", -1.0, rid=4)
    assert [s.name for s in tr.spans] == ["inner", "inner",
                                          "request.queue", "outer"]
    assert outer.parent == -1 and inner.parent == outer.id
    assert second.parent == outer.id and past.parent == -1
    assert (outer.rid, inner.lane) == (4, 2)
    assert inner.data == {"uploads": 3, "upload_bytes": 12}
    assert len({outer.id, inner.id, second.id, past.id}) == 4
    assert past.duration == 1.0
    assert tr.named("inner") == [inner, second]
    # a span of another tracer opened inside is not this one's child
    other = SpanTracer()
    with tr.span("a") as a:
        with other.span("b") as b:
            with tr.span("c") as c:
                pass
    assert b.parent == -1 and c.parent == a.id
    # spans stay out of the event digests
    assert tr.span_digest() == SpanTracer().span_digest()


def test_compile_lands_on_the_innermost_open_span():
    tr = SpanTracer()
    f = jax.jit(lambda x: x * 3 + 1)
    with tr.span("outer") as outer:
        with tr.span("inner") as inner:
            f(np.arange(7.0)).block_until_ready()
        f(np.arange(7.0)).block_until_ready()      # cached: no compile
    assert inner.data == {"compiles": 1}
    assert outer.data is None


def test_span_ring_is_bounded_and_counts_drops():
    tr = SpanTracer()
    tr.spans = collections.deque(maxlen=4)
    tr.begin_session(lambda: 0.0)
    for _ in range(6):
        with tr.span("s"):
            pass
    assert len(tr.spans) == 4 and tr.spans_dropped == 2
    tr.begin_session(lambda: 0.0)
    assert not tr.spans and tr.spans_dropped == 0


def _sim_requests(n, *, rate=8.0, plen=6, ntok=(3, 8), seed=3):
    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    return [Request(rid=i, prompt=rng.integers(0, 50, plen, dtype=np.int32),
                    max_tokens=int(rng.integers(*ntok)), arrival=float(t[i]))
            for i in range(n)]


def _sim_server(lanes, pool=None):
    bank = np.random.default_rng(0).random((64, N_NODES)).astype(np.float32)
    stepper = rt.SimStepper((FixedNodeStrategy(N_NODES, N_NODES - 1),),
                            bank, n_lanes=lanes, seg_time=0.05,
                            overhead=0.01, pool=pool)
    return rt.Server(stepper, rt.LaneScheduler(lanes), lambda r: 0)


def test_every_serve_records_into_the_process_tracer():
    requests = _sim_requests(12)
    _sim_server(2).serve(requests)
    iters = TRACER.named("server.iteration")
    waits = TRACER.named("request.queue")
    assert iters and not TRACER.spans_dropped
    assert sorted(s.rid for s in waits) == list(range(12))
    for s in waits:
        assert s.duration >= 0 and s.t0 == requests[s.rid].arrival
    blocked = TRACER.named("admission.blocked")
    assert blocked and {s.data["by"] for s in blocked} == {"lanes"}
    assert all(s.t1 >= s.t0 for s in TRACER.spans)
    # the next serve starts a new session: only its own spans remain
    _sim_server(2).serve(requests[:3])
    assert sorted(s.rid for s in TRACER.named("request.queue")) == [0, 1, 2]


def test_admission_blocked_by_pages_when_a_lane_is_free():
    """A pool too small for two requests at once leaves a lane free
    while the head waits: those waits are ``by=pages``."""
    pool = KVPool(n_lanes=3, page_size=4, lane_pages=4, n_pages=5)
    requests = _sim_requests(8, rate=50.0, plen=8, ntok=(6, 8))
    _sim_server(3, pool).serve(requests)
    by = {s.data["by"] for s in TRACER.named("admission.blocked")}
    assert "pages" in by


class _Boom(Exception):
    pass


def test_ring_survives_a_serve_that_ends_by_an_exception():
    server = _sim_server(2)
    inner = server.stepper.step
    n = [0]

    def step(*a, **k):
        n[0] += 1
        if n[0] == 6:
            raise _Boom
        return inner(*a, **k)

    server.stepper.step = step
    with pytest.raises(_Boom):
        server.serve(_sim_requests(10))
    iters = TRACER.named("server.iteration")
    assert len(iters) >= 6 and TRACER.named("request.queue")
    assert all(s.t1 >= s.t0 for s in iters)     # closed by the exception
    assert TRACER.spans[-1].name == "server.iteration"


def test_process_tracer_keeps_nothing_of_a_serve_alive():
    """After a serve, however it ended, the process tracer holds its
    spans and no reference to the server, so the stepper's device state
    is freed with it."""
    import gc
    import weakref
    for fail in (False, True):
        server = _sim_server(2)
        if fail:
            server.stepper.step = lambda *a, **k: 1 / 0
        try:
            server.serve(_sim_requests(4))
        except ZeroDivisionError:
            pass
        ref = weakref.ref(server.stepper)
        del server
        gc.collect()
        assert ref() is None and TRACER.named("server.iteration")


def test_profiler_events_join_the_spans_at_one_offset(tmp_path):
    from jax.profiler import ProfileData
    import time
    import warnings
    tr = SpanTracer()
    tr.begin_session(time.perf_counter)
    f = jax.jit(lambda x: x @ x)
    x = np.ones((64, 64), np.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(12):
            with tr.span("engine.step", rid=i):
                with tr.span("engine.sync"):
                    f(x).block_until_ready()
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    by_id = {s.id: s for s in tr.spans}
    offsets = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    sid = dict(e.stats).get("span_id")
                    if sid in by_id:
                        assert e.name == by_id[sid].name
                        offsets.append(e.start_ns * 1e-9 - by_id[sid].t0)
    assert len(offsets) == len(by_id) == 24
    assert max(offsets) - min(offsets) < 50e-6


# --------------------------------------------------------------------------
# the real engine
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    from repro.configs import get_config
    from repro.models import model as M
    from repro.models.param import materialize
    cfg = get_config("paper-ee-100m", smoke=True)
    params = materialize(M.model_defs(cfg), jax.random.PRNGKey(0))
    n = len(cfg.segments)
    stepper = rt.EngineStepper(
        params, cfg, (FixedNodeStrategy(n, n - 1),), n_lanes=2,
        cache_len=48, prompt_len=8, kv="paged", page_size=8,
        prefill_chunk=8, prefill_budget=8, paged_kernel=True)
    return cfg, params, stepper


def _engine_requests(cfg):
    rng = np.random.default_rng(5)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 5 + 4 * i,
                                               dtype=np.int32),
                    max_tokens=3 + i, arrival=0.0) for i in range(4)]


def test_engine_spans_change_no_token_and_follow_the_observer(engine):
    cfg, _, stepper = engine
    requests = _engine_requests(cfg)
    plain = rt.Server(stepper, rt.LaneScheduler(2), lambda r: 0).serve(
        requests)
    steps = TRACER.named("engine.step")
    assert steps and TRACER.named("engine.plan")
    assert {s.rid for s in TRACER.named("engine.admit")} == {0, 1, 2, 3}
    assert len(TRACER.named("pool.release")) == 4
    assert len(TRACER.named("pool.commit_prefix")) == 4
    for s in steps:
        assert s.data["uploads"] >= 5 and s.data["upload_bytes"] > 0
        assert s.data["record"].decode.shape[1] == 5
    n_process = len(TRACER.spans)
    obs = Observability()
    observed = rt.Server(stepper, rt.LaneScheduler(2), lambda r: 0,
                         obs=obs).serve(requests)
    for req in requests:
        assert plain.records[req.rid].tokens == \
            observed.records[req.rid].tokens, f"request {req.rid}"
    assert len(TRACER.spans) == n_process      # the observer's serve
    assert len(obs.tracer.named("engine.step")) == len(steps)
    stepper.spans = TRACER


def test_step_program_scopes_are_named_and_name_no_kernel(engine):
    _, params, stepper = engine
    args = (stepper.tok, stepper.caches, stepper.pos,
            np.ones(2, bool), np.zeros(2, np.int32))
    from repro.models.attention import PagedKV
    table = np.zeros(stepper.pool.table.shape, np.int32)
    kv = PagedKV(page_table=table, write_page=np.zeros(2, np.int32),
                 write_slot=np.zeros(2, np.int32))
    chunk, _, _ = stepper._build_chunk({})
    text = stepper._step.func.lower(
        params, *args, kv, stepper.states, chunk).as_text(debug_info=True)
    stacks = [n.split("/") for n in re.findall(r'loc\("([^"]+)"', text)]
    scopes = ("segment0", "segment1", "readout0", "chunk_sweep",
              "page_write")
    for scope in scopes:
        assert any(scope in parts for parts in stacks), scope
    for parts in stacks:
        if any(p.startswith(("segment", "readout", "chunk_sweep",
                             "page_write")) for p in parts):
            assert not any("paged_attention" in p or "paged_prefill" in p
                           for p in parts), "/".join(parts)
