#!/usr/bin/env python3
"""Derive the exit-depth profiles that the depth_profile traffic files pin.

    python3 bench/tools/derive_profile.py [--samples 200000] [--seed 0]

Runs the calibrated T-Tamer recall-index strategy (`recall_index`, lambda
0.5, node costs (1 - lambda) x the traces' segment costs, as the repo's
examples and runtime benchmark calibrate it) over
`repro.core.traces.ee_like_traces` with 6 nodes (paper-ee-100m) and 5
nodes (granite-3-2b), and prints for each node i < n-1 the share
of samples that probe past node i among those that reached it.  Those
shares are copied into the traffic files as data, so that later changes
to `repro.core` cannot move the benchmark's yardstick.  The derivation
needs no chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))


def continue_shares(n_nodes: int, samples: int, seed: int, lam: float):
    from repro.core.traces import ee_like_traces
    from repro.strategy import Cascade, evaluate, make
    losses, _, costs = ee_like_traces(np.random.default_rng(seed), samples,
                                      n_nodes)
    casc = Cascade.from_traces(losses, (1 - lam) * costs, lam=lam)
    probed = np.asarray(evaluate(make("recall_index", casc), losses).n_probed)
    reach = [(probed >= i + 1).sum() for i in range(n_nodes)]
    return [round(float(reach[i + 1] / reach[i]), 4) if reach[i] else 0.0
            for i in range(n_nodes - 1)], np.bincount(
                probed - 1, minlength=n_nodes).tolist()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--samples", type=int, default=200_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lam", type=float, default=0.5)
    args = ap.parse_args()
    for name, n in (("paper-ee-100m", 6), ("granite-3-2b", 5)):
        cont, hist = continue_shares(n, args.samples, args.seed, args.lam)
        print(json.dumps({"config": name, "nodes": n, "lambda": args.lam,
                          "continue": cont, "stop_node_histogram": hist}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
