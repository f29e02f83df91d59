#!/usr/bin/env python3
"""Readings that set a cell's ``logit_gap`` limit: the program's widest
gap and the control's, on several seeds, at the cell's own load.

    python3 bench/tools/control.py <cell> --seeds 1,2,3 [--seconds 10]

One process on one chip builds the cell once and, for each seed, draws
that seed's weights, serves the cell's mix for ``--seconds`` (plus its
drain, so the longest requests finish) and compares a sample of the
finished requests, as many as a benchmark run compares, with the plain
reference.  It prints per seed the program's widest gap and the
control's: the reference itself computed one precision below what the
configuration states (bfloat16 for float32 weights, float8 for
bfloat16), reading at each position the gap of the token it puts
first.  The limit lies between the largest program reading and the
smallest control reading.  Needs a TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

CONTROL = {"float32": "bfloat16", "bfloat16": "float8"}


def readings(spec: dict, seeds, seconds: float, root: str = ROOT):
    """Per seed: the program's widest gap and the control's (a dict).
    Each seed is built and served as a benchmark run is (weights first,
    then the stepper), and every seed is served before any reference
    runs, so the step and the reference never share the device."""
    import jax

    from bench import check, run, traffic as traffic_lib
    if jax.devices()[0].platform == "tpu":
        run.use_cache(root)
    config = spec["config"]
    traffic = traffic_lib.load(spec["cell"]["traffic"], spec["traffic"])
    close = (seconds if traffic.arrival == "saturate"
             else seconds + traffic.drain_s)
    served = []
    for seed in seeds:
        cfg, params, stepper, server, _ = run.build(spec, seed, root)
        run.warm_up(stepper, server, config, cfg.vocab)
        reqs = traffic_lib.make_requests(traffic, seed, seconds, cfg.vocab)
        logs, _ = run.serve_window(stepper, server, reqs, close)
        served.append((seed, check.sample(logs, traffic.sample, seed)))
        del params, stepper, server
        gc.collect()
    ref = run.reference_module(config, root)
    control = CONTROL[config["weight_dtype"]]
    for seed, picked in served:
        plain = ref.plain_params(config["sizes"], seed,
                                 jax.numpy.dtype(config["weight_dtype"]))
        gap, cgap, n = check.logit_gaps(
            ref, plain, config["sizes"], picked, seq=traffic.max_context,
            rows=int(traffic.output["max"]) + 1,
            kv_dtype=jax.numpy.dtype(config["kv_dtype"]), control=control)
        del plain
        gc.collect()
        yield {"cell": spec["cell"]["name"], "seed": seed,
               "requests": len(picked), "tokens": n, "program_gap": gap,
               "control": control, "control_gap": cgap}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    from bench import run
    spec = run.cell_spec(run.load_benchmark(), args.cell)
    run.require_devices(int(spec["cell"]["chips"]))
    seeds = [int(s) for s in args.seeds.split(",")]
    for out in readings(spec, seeds, args.seconds):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
