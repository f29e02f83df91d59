"""Host cost of the serving path's spans, with and without an active
``jax.profiler`` trace.

    PYTHONPATH=src python3 benchmarks/span_cost.py [--lanes 32] [--steps 2000]

Times the instrumentation of one fused step as `EngineStepper.step`
and `Server.serve` make it: the ``server.iteration`` and ``engine.step``
spans with the five children a step opens (plan, pool.prepare_step,
dispatch, sync, and one pool op), the step's counts, and its
`StepRecord` for ``--lanes`` decoding lanes.  The spans wrap no work,
so the time is what the instrumentation adds to a step.  Prints one
JSON line: microseconds per step and per span, profiler off and on.
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time

import numpy as np


def instrumented_step(tr, lanes: int, rids, pos, served, tok) -> None:
    from repro.serving.obs.trace import StepRecord
    with tr.span("server.iteration"):
        with tr.span("engine.step") as span:
            with tr.span("engine.plan"):
                with tr.span("pool.prepare_step"):
                    pass
            with tr.span("engine.dispatch"):
                pass
            with tr.span("pool.commit_prefix", rid=3, lane=1):
                pass
            with tr.span("engine.sync"):
                pass
            idx = np.flatnonzero(np.ones(lanes, bool))
            span.add(uploads=5, upload_bytes=4096, seg_batch=6,
                     seg_policy=lanes * 3,
                     record=StepRecord.of(
                         (idx, rids[idx], pos[idx], served[idx], tok[idx]),
                         [], (idx[:0], rids[:0], tok[:0])))


SPANS_PER_STEP = 7


def per_step_us(tr, lanes: int, steps: int) -> float:
    rids = np.arange(lanes, dtype=np.int64)
    pos = np.full(lanes, 100, np.int64)
    served = np.zeros(lanes, np.int32)
    tok = np.ones(lanes, np.int32)
    for _ in range(50):
        instrumented_step(tr, lanes, rids, pos, served, tok)
    t = time.perf_counter()
    for _ in range(steps):
        instrumented_step(tr, lanes, rids, pos, served, tok)
    return (time.perf_counter() - t) / steps * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--lanes", type=int, default=32)
    ap.add_argument("--steps", type=int, default=2000)
    args = ap.parse_args(argv)
    import jax

    from repro.serving.obs.trace import SpanTracer
    tr = SpanTracer()
    tr.begin_session(time.perf_counter)
    off = per_step_us(tr, args.lanes, args.steps)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            on = per_step_us(tr, args.lanes, args.steps)
        finally:
            jax.profiler.stop_trace()
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "lanes": args.lanes,
        "spans_per_step": SPANS_PER_STEP,
        "step_us_profiler_off": off, "step_us_profiler_on": on,
        "span_us_profiler_off": off / SPANS_PER_STEP,
        "span_us_profiler_on": on / SPANS_PER_STEP}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
