"""The continuous-batching serve loop + model-free simulation
(DESIGN.md §7).

Data flow per iteration:

    workload arrivals -> RequestQueue -> LaneScheduler.admit
        -> stepper.admit (prefill + lane scatter | sim cursor)
        -> stepper.step  (one token for every occupied lane)
        -> metrics.on_token / lane recycling on completion

`Server` drives either stepper behind one loop:

  * `EngineStepper` (scheduler.py) — the real model; time is wall time.
  * `SimStepper` (here) — model-free: each lane's token replays a row of
    per-node losses (calibration traces or synthetic) through the SAME
    strategy bank the engine would consult, and a virtual clock prices
    each step.  CI exercises queueing, admission, recycling, and metric
    plumbing in milliseconds with no model params at all.

The sim cost model prices a step as ``overhead + seg_time * work``
where work is the launched depth (``cost="batch"``, what the masked
batch engine pays) or the mean per-lane probes (``cost="lane"``, what a
lane-granular dispatch would pay — the accounting split DESIGN.md §3
describes).  Strategy quality only turns into throughput under the lane
model, which is exactly the regime the bench sweep reports.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.engine import bank_observe, bank_serve
from repro.serving.obs.trace import TRACER
from repro.serving.runtime.metrics import RuntimeMetrics
from repro.serving.runtime.request import Request, RequestQueue
from repro.serving.runtime.scheduler import LaneScheduler
from repro.strategy.base import dynamic_arrays, with_arrays

__all__ = ["Server", "SimStepper", "build_bank", "cascade_factory"]

_ROW_PRIME = 9973  # deterministic per-(rid, token) trace-row assignment


def build_bank(requests, make_strategy, default: tuple):
    """Resolve the distinct per-request ``(strategy, lam)`` pairs into a
    static strategy bank.

    Returns ``(strategies, sid_of)`` — the tuple the token step closes
    over (its size is fixed at trace time) and the lane->member resolver
    the scheduler stamps on each admission.  ``make_strategy(name, lam)``
    builds one member; ``default`` fills a request's missing fields.
    """
    def key_of(req):
        return (req.strategy or default[0],
                req.lam if req.lam is not None else default[1])

    keys: list = []
    for req in sorted(requests, key=lambda r: r.rid):
        k = key_of(req)
        if k not in keys:
            keys.append(k)
    if not keys:
        keys = [default]
    strategies = tuple(make_strategy(name, lam) for name, lam in keys)
    index = {k: i for i, k in enumerate(keys)}
    return strategies, lambda req: index[key_of(req)]


def cascade_factory(cascade):
    """The standard ``make_strategy`` for `build_bank`: registry dispatch
    against one calibrated cascade, with ``lam=None`` meaning the
    cascade's own lambda.  Callers with per-family CLI knobs (the
    launcher's thresholds/patience) wrap their own factory instead."""
    from repro import strategy as _strategy

    def mk(name, lam):
        if lam is None:
            return _strategy.make(name, cascade)
        return _strategy.make(name, cascade, lam=lam)

    return mk


class SimStepper:
    """Model-free stepper: replays loss traces through the strategy bank.

    ``trace_bank`` is a ``(T, n_nodes)`` array of per-node losses (e.g.
    `core.traces.ee_like_traces` or a cascade's calibration traces);
    request ``rid``'s token ``t`` deterministically reads row
    ``(rid * 9973 + t) % T``, so a request's decisions are independent
    of lane placement and arrival order by construction.

    Prefill cost model (DESIGN.md §9): ``prefill_tok_time`` prices one
    prompt token.  By default admission is STOP-THE-WORLD — the whole
    prompt's cost lands on the virtual clock as a SERIAL stall before
    the next step, exactly like the engine's batch-1 prefill program
    blocking the device queue.  With ``prefill_chunk`` set, admission
    is CHUNKED instead: the same `ChunkPlanner` the real engine uses
    spreads up to ``prefill_budget`` prompt tokens per step across
    admitting lanes, and the fused step is priced at the PIGGYBACK
    ROOFLINE ``max(decode cost, chunk cost)`` — single-token decode is
    memory-bound while the prefill chunk is compute-bound, so the
    co-scheduled chunk hides under the decode step's bandwidth time
    until it grows past it (the Sarathi observation; the budget knob
    is exactly the lever that keeps it hidden).  Lanes emit their
    first token on the step after their prefill completes.  Token
    DECISIONS are (rid, t)-keyed either way, so the two admission
    modes produce bit-identical streams by construction — only the
    clock moves.
    """

    virtual_time = True
    emits_tokens = False   # `emitted` carries served nodes, not token ids
    # observability plane (DESIGN.md §12): the server installs a
    # `SpanTracer` here when one is attached; every producer guards on
    # `is not None`, so an untraced serve pays nothing
    tracer = None
    last_loss = None       # per-lane served-node loss of the last step
    last_deepest = None    # per-lane deepest PROBED node (-1 = silent)
    # fault plane (DESIGN.md §14): the server stamps its clock here
    # each iteration when a FaultPlan is attached
    fault_now = 0.0

    def __init__(self, strategies: tuple, trace_bank, *, n_lanes: int,
                 seg_time: float = 1.0, overhead: float = 0.25,
                 cost: str = "lane", prefill_tok_time: float = 0.0,
                 prefill_chunk: int | None = None,
                 prefill_budget: int | None = None, pool=None,
                 faults=None):
        if cost not in ("lane", "batch"):
            raise ValueError(f"unknown cost model {cost!r}")
        from repro.serving.runtime.scheduler import ChunkPlanner
        # optional paged-KV admission gate (DESIGN.md §13): a real
        # `KVPool` doing its full host-side bookkeeping — reservation,
        # prefix sharing, per-token page growth and COW — with no device
        # arrays behind it.  The soak harness shrinks this pool to
        # manufacture genuine page pressure the invariant ledger audits.
        self.pool = pool
        self.faults = faults
        self.prefill_tok_time = float(prefill_tok_time)
        prefill_chunk = prefill_chunk or None      # 0 == disabled
        self.prefill_chunk = None if prefill_chunk is None \
            else int(prefill_chunk)
        self.planner = None if prefill_chunk is None else ChunkPlanner(
            self.prefill_chunk, prefill_budget)
        self.strategies = strategies
        self.bank = np.asarray(trace_bank, np.float32)
        self.n_nodes = self.bank.shape[1]
        self.full_depth = self.n_nodes
        self.n_lanes = int(n_lanes)
        self.seg_time = float(seg_time)
        self.overhead = float(overhead)
        self.cost = cost
        for s in strategies:
            if s.n_nodes != self.n_nodes:
                raise ValueError(
                    f"strategy expects {s.n_nodes} nodes, trace bank has "
                    f"{self.n_nodes}")
            if getattr(s, "needs_aux", False):
                raise ValueError(
                    f"{type(s).__name__} consumes the aux prediction "
                    "channel; simulation mode replays losses only — "
                    "serve it through the real EngineStepper instead")

        # hot-swap point (DESIGN.md §11): the decision program takes the
        # bank's dynamic arrays as a traced ARGUMENT, so publishing new
        # same-shaped tables (a `BankSwap`) hits the jit cache — never a
        # retrace, never a dropped lane.  ``bank_source`` is the control
        # plane's override; without one the baked arrays are passed.
        self._bank_arrays = tuple(dynamic_arrays(s) for s in strategies)
        self.bank_source = None
        # host tap for observed (loss-row, served-node) outcomes — the
        # Recalibrator's input stream; None = disabled, zero overhead
        self.row_tap = None

        def decide(arrays, losses, occupied, sid):
            live = tuple(with_arrays(s, a)
                         for s, a in zip(strategies, arrays))
            b = losses.shape[0]
            states = tuple(s.init(b) for s in live)
            active = occupied
            depth = jnp.zeros((), jnp.int32)
            policy = jnp.zeros((), jnp.int32)
            # per-lane deepest PROBED node — folded from the per-node
            # n_probed deltas, so it costs no extra strategy calls; the
            # regret meter's recall-forgone attribution reads it off
            # each token event
            deepest = jnp.full((b,), -1, jnp.int32)
            np_prev = jnp.zeros((b,), jnp.int32)

            def probed_of(states):
                out = states[0].n_probed
                for k in range(1, len(live)):
                    out = jnp.where(sid == k, states[k].n_probed, out)
                return out

            for node in range(self.n_nodes):
                depth = depth + active.any().astype(jnp.int32)
                policy = policy + active.sum(dtype=jnp.int32)
                states, active = bank_observe(
                    live, states, node, losses[:, node], None,
                    active, sid)
                np_now = probed_of(states)
                deepest = jnp.where(np_now > np_prev, node, deepest)
                np_prev = np_now
            return bank_serve(live, states, sid), depth, policy, deepest

        self._decide = jax.jit(decide)
        self.alloc()

    def bank_arrays(self) -> tuple:
        """The per-slot dynamic arrays the next step will decide with."""
        if self.bank_source is not None:
            return self.bank_source.bank_arrays()
        return self._bank_arrays

    def decide_cache_size(self) -> int:
        """Jit-cache entries of the decision program — the hot-swap
        safety tests assert this stays at 1 across swaps/publishes."""
        fn = getattr(self._decide, "_cache_size", None)
        return int(fn()) if fn is not None else -1

    def apply_gear(self, gear) -> None:
        """Host-side gear knobs outside the strategy tables: the
        chunked-prefill budget.  Routing (which slot new admissions
        use) and tables (recalibration) swap through ``bank_source``."""
        budget = getattr(getattr(gear, "spec", gear),
                         "prefill_budget", None)
        if budget is not None and self.planner is not None:
            self.planner.budget = int(budget)

    def alloc(self) -> None:
        self.lane_req: list[Request | None] = [None] * self.n_lanes
        self.lane_tidx = np.zeros(self.n_lanes, np.int64)
        self.lane_prefill = np.zeros(self.n_lanes, np.int64)
        self._stall = 0.0          # stop-the-world prefill debt
        # served-loss accumulator: the sim knows the served node's trace
        # loss exactly, which is the quality axis the cascade-vs-
        # monolith Pareto sweep compares on
        self.served_loss_sum = 0.0
        self.served_loss_n = 0
        self._stall_seen: set = set()   # (model, window-start) emitted
        if self.pool is not None:
            self.pool.reset()

    def _note_stall(self, model: int) -> None:
        """Emit one `rung_stall` span per scripted window edge."""
        win = self.faults.stall_window(model, self.fault_now)
        if win is None or (model, win[0]) in self._stall_seen:
            return
        self._stall_seen.add((model, win[0]))
        if self.tracer is not None:
            self.tracer.emit("rung_stall", model=model,
                             t0=round(win[0], 9), until=round(win[1], 9))

    def reserve(self, req: Request) -> bool:
        """Admission gate: with a pool attached, reserve the request's
        worst-case page need (or leave it queued); gate-free otherwise."""
        if self.pool is None:
            return True
        return self.pool.reserve(req.prompt, req.max_tokens)

    def release(self, lane: int) -> None:
        self.lane_prefill[lane] = 0     # reaped mid-prefill: drop debt
        if self.pool is not None:
            self.pool.release(lane)

    def admit(self, lane: int, req: Request) -> None:
        self.lane_req[lane] = req
        self.lane_tidx[lane] = 0
        lp = len(req.prompt)
        if self.pool is not None:
            self.pool.admit(lane, req.prompt, req.max_tokens)
        if self.prefill_chunk is not None:
            self.lane_prefill[lane] = lp
        elif self.prefill_tok_time > 0.0:
            # stop-the-world: the whole prompt stalls the next step
            self._stall += lp * self.prefill_tok_time

    def warmup(self) -> None:
        """Compile the decision program (virtual time is unaffected)."""
        self._decide(self.bank_arrays(),
                     jnp.zeros((self.n_lanes, self.n_nodes), jnp.float32),
                     jnp.zeros((self.n_lanes,), bool),
                     jnp.zeros((self.n_lanes,), jnp.int32))
        self.alloc()

    def _row(self, req: Request, tidx: int) -> np.ndarray:
        return self.bank[(req.rid * _ROW_PRIME + tidx) % len(self.bank)]

    def step(self, occupied: np.ndarray, sid: np.ndarray):
        """Returns ``(emitted, served, seg_batch, seg_policy, cost,
        emit_mask)`` — lanes mid-prefill are occupied but emit nothing
        and consume no trace row."""
        occupied = np.asarray(occupied, bool)
        if (self.faults is not None
                and self.faults.stall_active(0, self.fault_now)):
            # the single sim rung is frozen: no rows consumed, no
            # tokens, no prefill progress — only the clock moves, so a
            # finite window always passes (liveness)
            self._note_stall(0)
            if self.tracer is not None:
                self.last_loss = np.full(self.n_lanes, np.nan)
                self.last_deepest = np.full(self.n_lanes, -1)
            served = np.zeros(self.n_lanes, np.int64)
            return (served, served, 0, 0, self.overhead,
                    np.zeros(self.n_lanes, bool))
        emit = occupied.copy()
        stall = self._stall                 # stop-the-world: serial
        self._stall = 0.0
        chunk_cost = 0.0                    # chunked: piggybacked
        if self.prefill_chunk is not None:
            prefilling = occupied & (self.lane_prefill > 0)
            emit &= ~prefilling
            if prefilling.any():
                widths = self.planner.plan({
                    int(lane): (int(self.lane_prefill[lane]),
                                len(self.lane_req[lane].prompt))
                    for lane in np.flatnonzero(prefilling)})
                for lane, w in widths.items():
                    self.lane_prefill[lane] -= w
                    chunk_cost += w * self.prefill_tok_time
                    if self.tracer is not None:
                        self.tracer.emit(
                            "prefill_chunk", lane=lane,
                            rid=self.lane_req[lane].rid, width=int(w),
                            left=int(self.lane_prefill[lane]))
        if self.pool is not None and emit.any():
            # real paged bookkeeping per decode token: fresh tail pages
            # from the reserved budget, COW splits on shared tails —
            # the reservation guarantees these can never fail mid-stream
            self.pool.prepare_step(emit)
            self.pool.note_written(emit)
        losses = np.zeros((self.n_lanes, self.n_nodes), np.float32)
        for lane in np.flatnonzero(emit):
            losses[lane] = self._row(self.lane_req[lane],
                                     int(self.lane_tidx[lane]))
            self.lane_tidx[lane] += 1
        served, depth, policy, deepest = jax.device_get(self._decide(
            self.bank_arrays(), jnp.asarray(losses),
            jnp.asarray(emit, bool), jnp.asarray(sid, jnp.int32)))
        for lane in np.flatnonzero(emit):
            self.served_loss_sum += float(losses[lane, served[lane]])
            self.served_loss_n += 1
        if self.tracer is not None:
            # per-lane served-node loss, picked up by the server's token
            # events for decision attribution (NaN = no emission)
            served_np = np.asarray(served)
            self.last_loss = np.where(
                emit, losses[np.arange(self.n_lanes),
                             np.clip(served_np, 0, self.n_nodes - 1)],
                np.nan)
            self.last_deepest = np.where(emit, np.asarray(deepest), -1)
        if self.row_tap is not None and emit.any():
            idx = np.flatnonzero(emit)
            self.row_tap(losses[idx], np.asarray(served)[idx])
        work = (policy / self.n_lanes) if self.cost == "lane" else depth
        # piggyback roofline: the compute-bound chunk hides under the
        # memory-bound decode sweep; the serial stop-the-world stall
        # cannot (it is its own batch-1 program on the device queue)
        cost = self.overhead + max(self.seg_time * float(work),
                                   chunk_cost) + stall
        # sim tokens have no content; the served node stands in
        return served, served, int(depth), int(policy), cost, emit

    @property
    def mean_served_loss(self) -> float | None:
        if not self.served_loss_n:
            return None
        return self.served_loss_sum / self.served_loss_n


class Server:
    """Open-loop continuous-batching server over any stepper."""

    def __init__(self, stepper, scheduler: LaneScheduler, sid_of, *,
                 order: str = "fifo", slo: float | None = None,
                 static_batching: bool = False, eos: int | None = None,
                 controller=None, obs=None,
                 enforce_deadlines: bool = False):
        self.stepper = stepper
        self.scheduler = scheduler
        self.sid_of = sid_of
        self.order = order
        self.slo = slo
        self.static_batching = static_batching
        self.eos = eos
        # fault plane (DESIGN.md §14): deadlines double as EDF ordering
        # hints, so reaping on expiry is opt-in — `cancel_at` (a client
        # hang-up) is always enforced when present
        self.enforce_deadlines = bool(enforce_deadlines)
        # observability plane (DESIGN.md §12): an `Observability` bundle
        # — tracer + optional flight recorder.  The server binds its own
        # clock to the tracer (virtual in sim mode, so traces are
        # exactly deterministic) and installs it on the stepper and
        # controller; None means zero overhead everywhere.
        self.obs = obs
        # adaptive control plane (DESIGN.md §11): begin() binds it to
        # the metrics + stepper, on_arrivals feeds the load signal,
        # on_step_end is the step-boundary decision point — the ONLY
        # instant a gear swap can land, which is what makes swaps
        # atomic with respect to in-flight token steps
        self.controller = controller
        self._vt = 0.0
        self._t0 = 0.0

    # ---- clock ---------------------------------------------------------
    def _now(self) -> float:
        if self.stepper.virtual_time:
            return self._vt
        return time.perf_counter() - self._t0

    def _advance_to(self, t: float) -> None:
        if self.stepper.virtual_time:
            self._vt = max(self._vt, t)
        else:
            gap = t - self._now()
            if gap > 0:
                time.sleep(gap)

    # ---- fault plane ---------------------------------------------------
    def _reap_status(self, req, now: float) -> str | None:
        """Terminal status a live request has earned by ``now``, or
        None.  Cancellation wins ties — a hung-up client's deadline is
        moot."""
        if req.cancel_at is not None and req.cancel_at <= now:
            return "cancelled"
        if (self.enforce_deadlines and req.deadline is not None
                and req.deadline <= now):
            return "timed_out"
        return None

    def _reap(self, queue, metrics, tracer, release, now: float) -> None:
        """Sweep cancelled / expired requests out of the queue and off
        their lanes between steps.  Lane teardown runs release-first so
        the span events land on an already-clean pool — the ledger's
        `cancel_releases_pages` probe reads pool state at the event."""
        sched = self.scheduler
        for req in queue.reap(
                lambda r: self._reap_status(r, now) is not None):
            status = self._reap_status(req, now)
            metrics.on_reap(req, now, status)
            if tracer is not None:
                kind = ("cancel" if status == "cancelled"
                        else "deadline_miss")
                tracer.emit(kind, rid=req.rid)
        for lane in np.flatnonzero(sched.occupied_mask()):
            req = sched.lane_req[lane]
            status = self._reap_status(req, now)
            if status is None:
                continue
            if release is not None:
                release(int(lane))  # KV pages + escalation lanes freed
            sched.release(int(lane))
            metrics.on_reap(req, now, status)
            if tracer is not None:
                kind = ("cancel" if status == "cancelled"
                        else "deadline_miss")
                tracer.emit(kind, rid=req.rid, lane=int(lane))

    def _fault_wake(self, queue, faults, reaping: bool,
                    now: float) -> float | None:
        """Earliest future instant at which the fault plane changes the
        picture for a queue that cannot admit right now: a queued
        request's reap time, or a scripted stall/squeeze boundary."""
        wake = None
        if reaping:
            for r in queue.requests():
                for t in (r.cancel_at,
                          r.deadline if self.enforce_deadlines else None):
                    if t is not None and t > now and (wake is None
                                                      or t < wake):
                        wake = t
        if faults is not None:
            nc = faults.next_change(now)
            if nc is not None and (wake is None or nc < wake):
                wake = nc
        return wake

    # ---- the loop ------------------------------------------------------
    def serve(self, requests, warmup: bool = True) -> RuntimeMetrics:
        """Run the full open-loop session: admit every request at its
        arrival time, decode until all streams drain, return metrics.

        ``warmup`` compiles the stepper's device programs before the
        serving clock starts, so wall-clock latency percentiles measure
        serving, not XLA compilation.

        The serve's spans go to ``obs.tracer``, else to the process
        tracer `TRACER`, whose ring keeps them after the serve however
        it ends; the process tracer then lets go of this server's clock,
        so it keeps nothing of the serve alive but its spans.
        """
        try:
            return self._serve(requests, warmup)
        finally:
            if self.obs is None:
                TRACER.bind_clock(None)

    def _serve(self, requests, warmup: bool) -> RuntimeMetrics:
        sched = self.scheduler
        stepper = self.stepper
        # step- and request-granular spans are always on (DESIGN.md
        # §12): into the Observability's tracer, else the process's
        spans = self.obs.tracer if self.obs is not None else TRACER
        if hasattr(stepper, "spans"):
            stepper.spans = spans
        if warmup:
            stepper.warmup()
        else:
            stepper.alloc()
        metrics = RuntimeMetrics(stepper.full_depth, sched.n_lanes)
        if self.controller is not None:
            self.controller.begin(metrics, stepper)
        tracer = self.obs.tracer if self.obs is not None else None
        if tracer is not None:
            tracer.bind_clock(self._now)
            stepper.tracer = tracer
            if self.controller is not None:
                self.controller.tracer = tracer
            flight = self.obs.flight
            if flight is not None:
                if flight.slo is None:
                    flight.slo = self.slo
                flight.bind(tracer,
                            snapshot_fn=lambda: metrics.summary(self.slo))
            ledger = getattr(self.obs, "ledger", None)
            if ledger is not None:
                ledger.bind(tracer, pool=getattr(stepper, "pool", None))
            regret = getattr(self.obs, "regret", None)
            if regret is not None:
                # pure listener, same discipline as the ledger: the
                # meter pulls the stepper's trace bank for its exact
                # oracle but never emits or syncs anything itself
                regret.bind(tracer, stepper=stepper, flight=flight,
                            controller=self.controller)
        deadline_of = None
        if self.order == "edf" and self.slo is not None:
            deadline_of = lambda r: r.arrival + self.slo  # noqa: E731
        queue = RequestQueue(self.order, deadline_of=deadline_of)
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        # fault plane (DESIGN.md §14): the stepper may carry a FaultPlan
        # whose serve-borne windows (rung stalls, page squeezes) are
        # read off the virtual clock each iteration; request-borne
        # faults ride the requests themselves
        faults = getattr(stepper, "faults", None)
        # the degrade governor reads the clock too: its deadline-budget
        # check needs `now` even when no FaultPlan is attached
        clocked = (faults is not None
                   or getattr(stepper, "governor", None) is not None)
        reaping = self.enforce_deadlines or any(
            r.cancel_at is not None for r in pending)
        self._vt = 0.0
        self._t0 = time.perf_counter()
        metrics.t_start = self._now()
        spans.begin_session(self._now)

        # paged-KV steppers gate admission on their free-page budget
        # (reserve-at-pop); a blocked request waits at the queue head
        gate = getattr(stepper, "reserve", None)
        release = getattr(stepper, "release", None)
        refused = [False]       # the gate refused the head this pass
        if gate is not None:
            def gate(req, _inner=gate):
                ok = _inner(req)
                if not ok:
                    refused[0] = True
                    if tracer is not None:
                        tracer.emit("page_blocked", rid=req.rid)
                return ok
        # (start, cause) of the wait since the last admission pass that
        # left the queue non-empty: no free lane, or no pages for one
        blocked = None

        while pending or len(queue) or sched.busy():
            with spans.span("server.iteration"):
                now = self._now()
                if clocked:
                    stepper.fault_now = now
                if faults is not None:
                    pool = getattr(stepper, "pool", None)
                    if pool is not None and hasattr(pool, "set_squeeze"):
                        pool.set_squeeze(faults.squeeze_pages(now))
                pushed = []
                while pending and pending[0].arrival <= now:
                    req = pending.pop(0)
                    queue.push(req)
                    pushed.append(req.arrival)
                    if tracer is not None:
                        # self-contained for replay (obs/replay.py): the
                        # queued event carries everything needed to rebuild
                        # the request — prompt bytes included, since paged
                        # admission and prefix sharing key on content
                        extra = {"plen": len(req.prompt),
                                 "ntok": int(req.max_tokens),
                                 "prompt": np.asarray(
                                     req.prompt, np.uint32).tobytes().hex()}
                        if req.strategy is not None:
                            extra["strategy"] = req.strategy
                        if req.lam is not None:
                            extra["lam"] = float(req.lam)
                        if req.deadline is not None:
                            extra["deadline"] = float(req.deadline)
                        if req.cancel_at is not None:
                            extra["cancel_at"] = float(req.cancel_at)
                        tracer.emit("queued", t=req.arrival, rid=req.rid,
                                    **extra)
                if self.controller is not None and pushed:
                    self.controller.on_arrivals(pushed)
                if reaping:
                    self._reap(queue, metrics, tracer, release, now)
                refused[0] = False
                for lane, req in sched.admit(
                        queue, self.sid_of,
                        static_batching=self.static_batching,
                        can_admit=gate):
                    stepper.admit(lane, req)
                    t_adm = self._now()
                    metrics.on_admit(req, t_adm)
                    spans.record("request.queue", req.arrival, t_adm,
                                 rid=req.rid, lane=lane)
                    if tracer is not None:
                        tracer.emit("admitted", rid=req.rid, lane=lane,
                                    sid=int(sched.sid[lane]))
                t_pass = self._now()
                if blocked is not None:
                    spans.record("admission.blocked", blocked[0], t_pass,
                                 by=blocked[1])
                blocked = None
                if len(queue):
                    blocked = (t_pass, "pages" if refused[0] else "lanes")
                if not sched.busy():
                    if not pending:
                        # nothing running, nothing arriving — but the queue
                        # may still hold page-blocked requests; one more
                        # admit pass runs next iteration after lanes/pages
                        # freed (len(queue) keeps the loop alive).  Guard
                        # against a request that can NEVER be admitted —
                        # unless the fault plane will change the picture (a
                        # queued request about to be reaped, a squeeze or
                        # stall window about to end): then jump there.
                        if len(queue):
                            wake = self._fault_wake(queue, faults, reaping,
                                                    now)
                            if wake is not None and wake > now:
                                self._advance_to(wake)
                                continue
                            raise RuntimeError(
                                "admission deadlock: queued requests but no "
                                "lane busy and no pending arrivals")
                        break
                    # every lane idle and nothing admissible: jump (sim) or
                    # sleep (real) to the next arrival
                    self._advance_to(pending[0].arrival)
                    continue

                occupied = sched.occupied_mask()
                out = stepper.step(occupied, sched.sid)
                if stepper.virtual_time:
                    emitted, served, sb, sp, cost, emit = out
                    self._vt += cost
                else:
                    emitted, served, sb, sp, emit = out
                tnow = self._now()
                # emit marks lanes whose entry is a real token this step;
                # lanes mid-(chunked-)prefill are occupied but still silent
                metrics.on_step(sb, sp, int(np.asarray(emit).sum()))
                for lane in np.flatnonzero(emit):
                    req = sched.lane_req[lane]
                    metrics.on_token(req.rid, int(served[lane]), tnow,
                                     token=int(emitted[lane]))
                    if tracer is not None:
                        extra = {}
                        rec = metrics.records[req.rid]
                        if rec.n_tokens == 1 and rec.ttft is not None:
                            extra["ttft"] = round(rec.ttft, 9)
                        ll = getattr(stepper, "last_loss", None)
                        if ll is not None and not np.isnan(ll[lane]):
                            extra["loss"] = round(float(ll[lane]), 6)
                        le = getattr(stepper, "last_escalated", None)
                        if le is not None and le[lane]:
                            extra["esc"] = True
                        ld = getattr(stepper, "last_deepest", None)
                        if ld is not None and ld[lane] >= 0:
                            extra["deepest"] = int(ld[lane])
                        if getattr(stepper, "emits_tokens", True):
                            extra["tok"] = int(emitted[lane])
                        tracer.emit("token", rid=req.rid, lane=int(lane),
                                    node=int(served[lane]),
                                    sid=int(sched.sid[lane]), **extra)
                    done = sched.consume_token(lane)
                    if (not done and self.eos is not None
                            and getattr(stepper, "emits_tokens", True)
                            and int(emitted[lane]) == self.eos):
                        done = True  # stream early-exit: recycle immediately
                    if done:
                        metrics.on_finish(req.rid, tnow)
                        if release is not None:
                            release(lane)   # paged KV: pages back to the pool
                        sched.release(lane)
                        if tracer is not None:
                            tracer.emit("finish", rid=req.rid, lane=int(lane))
                if tracer is not None:
                    data = {"queue": len(queue)}
                    pool = getattr(stepper, "pool", None)
                    if pool is not None:
                        data["pages_in_use"] = int(pool.pages_in_use)
                    tracer.emit("counter", **data)
                if self.controller is not None:
                    # step boundary: the device program for this step has
                    # fully retired, no lane is mid-token — the one atomic
                    # instant a gear swap / table publish may land
                    self.controller.on_step_end(self._now(), len(queue))

        metrics.t_end = self._now()
        if self.obs is not None:
            if getattr(self.obs, "ledger", None) is not None:
                self.obs.ledger.finalize(self._now())
            if getattr(self.obs, "regret", None) is not None:
                self.obs.regret.finalize(self._now())
        return metrics
