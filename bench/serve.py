"""Drive the system under test: `Server.serve` over the paged, chunked
`EngineStepper` with the Pallas kernels, and note what each step did.

`RecordingStepper` is the program's `EngineStepper` with the benchmark's
bookkeeping around each call: the host time of each admission and
step, which lanes decoded at which position and node, which lanes
prefilled which chunk, and each request's tokens with their times and
served nodes.  It reads only what the stepper returns and its pool's
host-side tables; the device programs are the program's own.

The first token of a request is the head's argmax of its final prefill
chunk, which the step returns for that lane though the server does not
record it: the stepper notes it here, at the time the step returned.
So time to first token is the time to that token, and the first
inter-token gap is from it to the first decoded token.

The window closes by raising `WindowClosed` from the next step once
the server's clock passes ``stop_at``: requests still queued or
decoding then stay as they are.

The program records no prefill progress and no first token, so this
module reads fields of the program that are not its public interface:
`EngineStepper._prefilling` (lane -> dict with the prompt's ``cursor``),
`EngineStepper.pool.seq_len` (tokens held per lane), the first five
items of `EngineStepper.step`'s return (tokens, served nodes, segment
counters, emitted lanes), `EngineStepper.pos`, `Server._now` and
`Server.scheduler` (a fresh `LaneScheduler(n_lanes)` per window).
`require_program_fields` checks them before a run, and `step` checks
what it reads, so a change to them stops the run with a message that
names the field rather than reading a wrong number.
"""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

from repro.serving.runtime.scheduler import EngineStepper

__all__ = ["WindowClosed", "ProgramChanged", "RequestLog",
           "RecordingStepper", "require_program_fields"]


class WindowClosed(Exception):
    """The measured window (and its drain) is over."""


class ProgramChanged(RuntimeError):
    """A private field of the program that the benchmark reads has
    changed shape."""


def _need(ok: bool, field: str, want: str) -> None:
    if not ok:
        raise ProgramChanged(
            f"bench/serve.py reads {field} of the program, which is no "
            f"longer {want}; the benchmark's step records depend on it")


def require_program_fields(stepper, server) -> None:
    """Check the program's private fields this module reads (see the
    module docstring) before a run."""
    _need(isinstance(getattr(stepper, "_prefilling", None), dict),
          "EngineStepper._prefilling", "a dict of lane -> prefill state")
    seq = getattr(getattr(stepper, "pool", None), "seq_len", None)
    _need(isinstance(seq, np.ndarray) and seq.shape == (stepper.n_lanes,),
          "EngineStepper.pool.seq_len", "an array of tokens per lane")
    _need(hasattr(stepper, "pos"), "EngineStepper.pos",
          "the lanes' device positions")
    _need(callable(getattr(server, "_now", None)), "Server._now",
          "the server's clock")
    _need(getattr(getattr(server, "scheduler", None), "n_lanes", None)
          == stepper.n_lanes, "Server.scheduler",
          "a LaneScheduler over the stepper's lanes")


@dataclasses.dataclass
class RequestLog:
    rid: int
    due: float
    prompt: np.ndarray
    budget: int
    admitted: float | None = None
    finished: float | None = None
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    nodes: list = dataclasses.field(default_factory=list)

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def done(self) -> bool:
        return self.finished is not None

    @property
    def first(self) -> float | None:
        return self.times[0] if self.times else None

    @property
    def n_decoded(self) -> int:
        return max(0, len(self.tokens) - 1)


class RecordingStepper(EngineStepper):
    """`EngineStepper` with the benchmark's bookkeeping around each call
    (see the module docstring)."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.clock = None          # the server's own clock
        self.stop_at = None
        self.on_step = None        # hook(now) before each step
        self.annotate = None       # span factory: annotate(name)
        self.logs: dict = {}
        self.steps: list = []
        self.lane_rid: dict = {}

    def _span(self, name):
        if self.annotate is None:
            return contextlib.nullcontext()
        return self.annotate(name)

    def admit(self, lane, req):
        with self._span("bench_admit"):
            super().admit(lane, req)
        if self.clock is None:
            return
        log = self.logs.setdefault(req.rid, RequestLog(
            rid=req.rid, due=req.arrival, prompt=req.prompt,
            budget=int(req.max_tokens)))
        log.admitted = self.clock()
        self.lane_rid[lane] = req.rid

    def release(self, lane):
        with self._span("bench_release"):
            super().release(lane)
        rid = self.lane_rid.pop(lane, None)
        if rid is not None:
            self.logs[rid].finished = self.clock()

    def step(self, occupied, sid, walk=None):
        if self.clock is None:
            return super().step(occupied, sid, walk)
        t0 = self.clock()
        if self.stop_at is not None and t0 >= self.stop_at:
            raise WindowClosed
        if self.on_step is not None:
            self.on_step(t0)
        _need(all("cursor" in st for st in self._prefilling.values()),
              "EngineStepper._prefilling[lane]", "a dict with 'cursor'")
        before = {ln: st["cursor"] for ln, st in self._prefilling.items()}
        occ = np.asarray(occupied, bool)
        ctx = self.pool.seq_len.copy()
        with self._span("bench_step"):
            out = super().step(occupied, sid, walk)
        t1 = self.clock()
        _need(isinstance(out, tuple) and len(out) >= 5,
              "EngineStepper.step's return",
              "(tokens, served, seg_batch, seg_policy, emit, ...)")
        tok, served, sb, sp, emit = out[:5]
        chunks = []
        for ln, cur in before.items():
            st = self._prefilling.get(ln)
            done = st is None
            log = self.logs[self.lane_rid[ln]]
            end = log.prompt_len if done else st["cursor"]
            if end > cur:
                chunks.append((int(cur), int(end - cur), done))
            if done:
                log.times.append(t1)
                log.tokens.append(int(tok[ln]))
                log.nodes.append(-1)        # the head, full depth
        decode = []
        for ln in np.flatnonzero(np.asarray(emit) & occ):
            log = self.logs[self.lane_rid[int(ln)]]
            log.times.append(t1)
            log.tokens.append(int(tok[ln]))
            log.nodes.append(int(served[ln]))
            decode.append((int(ctx[ln]) + 1, int(served[ln])))
        self.steps.append({"t0": t0, "t1": t1, "decode": decode,
                           "chunks": chunks, "seg_batch": int(sb),
                           "seg_policy": int(sp)})
        return out
