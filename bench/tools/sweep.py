#!/usr/bin/env python3
"""Find a cell's knee: serve its mix at several arrival rates, once each.

    python3 bench/tools/sweep.py <cell> --rates 2,4,6,8 [--seconds 15] [--seed N]

One process on one chip builds the cell once (weights, pool, kernels,
warm-up) and serves the cell's traffic at each rate in turn, for
``--seconds`` plus the mix's drain.  For each rate it prints one JSON
line: requests, how many got no first token, time to first token p50
and p90 over the whole window and over its last third alone, the
inter-token p99, output tokens/s and the requests still queued when the
window closed.  A rate the system sustains finishes every request and
keeps the last third's TTFT near the whole window's; past the knee the
queue grows through the window.  The cell's rate is then set at about
four fifths of the highest rate sustained.  Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def summarize(logs, seconds: float, close: float) -> dict:
    from bench.run import end_to_end, pct
    out = end_to_end(logs, seconds, close)
    late = [((g.first if g.first is not None else close) - g.due) * 1e3
            for g in logs if g.due >= seconds * 2 / 3]
    out["ttft_p50_ms"] = pct([((g.first if g.first is not None else close)
                               - g.due) * 1e3 for g in logs], 50)
    out["ttft_p90_last_third_ms"] = pct(late, 90)
    out["no_first_token"] = sum(1 for g in logs if g.first is None)
    out["queued_at_close_of_window"] = sum(
        1 for g in logs if g.admitted is None or g.admitted > seconds)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=12345)
    args = ap.parse_args()

    from bench import run, traffic as traffic_lib
    bench = run.load_benchmark()
    spec = run.cell_spec(bench, args.cell)
    run.require_devices(int(spec["cell"]["chips"]))
    run.use_cache(ROOT)
    cfg, params, stepper, server, _ = run.build(spec, args.seed)
    run.warm_up(stepper, server, spec["config"], cfg.vocab)
    for rate in (float(r) for r in args.rates.split(",")):
        tspec = dict(spec["traffic"], rate=rate)
        traffic = traffic_lib.load(args.cell, tspec)
        reqs = traffic_lib.make_requests(traffic, args.seed, args.seconds,
                                         cfg.vocab)
        close = args.seconds + traffic.drain_s
        logs, steps = run.serve_window(stepper, server, reqs, close)
        out = {"cell": args.cell, "rate": rate, "requests": len(reqs),
               "steps": len(steps),
               "chunk_steps": sum(1 for s in steps if s["chunks"])}
        out.update(summarize(logs, args.seconds, close))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
