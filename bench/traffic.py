"""The one traffic generator: a traffic file's parameters -> requests.

A traffic file (``bench/traffic/<mix>.json``) holds:

  * ``arrival``: ``"poisson"`` with ``rate`` (requests/s) -- an open
    loop over the measured window -- or ``"saturate"`` with
    ``requests``: that many requests all due at t = 0, so the queue
    never empties;
  * ``prompt`` and ``output``: length distributions, each
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}`` (inclusive, tokens);
  * ``exits``: ``{"policy": "depth_profile", "continue": [...],
    "members": m}`` or ``{"policy": "always_last"}``;
  * ``drain_s``: how long after the window the run waits for requests
    due inside it; ``sample``: how many finished requests the
    correctness check compares.

Every seed gets the same schedule.  For n requests the lengths are the
distribution's quantiles at (i + 1/2) / n and the Poisson gaps are the
exponential's quantiles at the same points, in one fixed order
(`SCHEDULE_SEED`); the seed draws only the prompt token ids (and,
elsewhere, the weights).  The order is stratified: every run of
`GROUP` consecutive requests holds one value from each of `GROUP`
strata of the sorted list, so every stretch of the window sees the
same mix of long and short requests and gaps.  With the order drawn
from the seed, six seeds of `granite2b.rag` spread 25% in TTFT p90 on a
TPU v5e while each seed's two runs agreed within 1-4%: the order
decided how much work fell near the window's end.  Token ids and
weights change no shape and no exit depth (`depth_profile` decides by
request id and token index), so two seeds serve the same work.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

__all__ = ["Traffic", "load", "lengths", "stratified", "make_requests"]

GROUP = 8
SCHEDULE_SEED = 0


@dataclasses.dataclass(frozen=True)
class Traffic:
    name: str
    arrival: str
    rate: float | None
    requests: int | None
    prompt: dict
    output: dict
    exits: dict
    drain_s: float
    sample: int

    @property
    def max_context(self) -> int:
        return int(self.prompt["max"]) + int(self.output["max"])


def load(name: str, spec: dict) -> Traffic:
    arrival = spec["arrival"]
    if arrival == "poisson":
        if not spec.get("rate", 0) > 0:
            raise ValueError(f"traffic {name}: poisson needs a rate > 0")
    elif arrival == "saturate":
        if not spec.get("requests", 0) > 0:
            raise ValueError(f"traffic {name}: saturate needs requests > 0")
    else:
        raise ValueError(f"traffic {name}: unknown arrival {arrival!r}")
    policy = spec["exits"]["policy"]
    if policy not in ("depth_profile", "always_last"):
        raise ValueError(f"traffic {name}: unknown exit policy {policy!r}")
    for key in ("prompt", "output"):
        d = spec[key]
        if not 1 <= d["min"] <= d["max"]:
            raise ValueError(f"traffic {name}: bad {key} range {d}")
    return Traffic(name=name, arrival=arrival, rate=spec.get("rate"),
                   requests=spec.get("requests"), prompt=spec["prompt"],
                   output=spec["output"], exits=spec["exits"],
                   drain_s=float(spec["drain_s"]),
                   sample=int(spec["sample"]))


def _quantile(dist: dict, q: float) -> float:
    if dist["dist"] == "lognormal":
        return dist["median"] * math.exp(
            dist["sigma"] * NormalDist().inv_cdf(q))
    if dist["dist"] == "uniform":
        lo, hi = dist["min"], dist["max"] + 1
        return lo + q * (hi - lo)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def lengths(dist: dict, n: int) -> np.ndarray:
    """The n lengths every seed gets: quantiles at (i + 1/2) / n,
    clipped to [min, max]."""
    qs = (np.arange(n) + 0.5) / n
    out = np.array([math.floor(_quantile(dist, q)) for q in qs], np.int64)
    return np.clip(out, dist["min"], dist["max"])


def stratified(rng, values, group: int = GROUP) -> np.ndarray:
    """``values`` in a seeded order whose every run of ``group``
    consecutive entries takes one value from each of ``group`` strata
    of the sorted values."""
    values = np.sort(np.asarray(values))
    m = -(-len(values) // group)
    strata = [rng.permutation(values[s * m:(s + 1) * m])
              for s in range(group)]
    out = []
    for g in range(m):
        run = [st[g] for st in strata if g < len(st)]
        out += [run[i] for i in rng.permutation(len(run))]
    return np.asarray(out)


def make_requests(traffic: Traffic, seed: int, seconds: float,
                  vocab: int) -> list:
    """Requests of one run: due times in [0, seconds) (or all at 0) and
    decode budgets from the mix, the same for every seed; prompts of
    token ids drawn from the seed."""
    from repro.serving.runtime.request import Request

    order = np.random.default_rng(SCHEDULE_SEED)
    if traffic.arrival == "poisson":
        n = max(1, round(traffic.rate * seconds))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / traffic.rate
        gaps = stratified(order, gaps)
        due = np.cumsum(gaps) - gaps[0]
        due *= seconds * (1 - 0.5 / n) / max(due[-1], 1e-9)
    else:
        n = int(traffic.requests)
        due = np.zeros(n)
    plen = stratified(order, lengths(traffic.prompt, n))
    olen = stratified(order, lengths(traffic.output, n))
    tokens = np.random.default_rng(seed)
    return [Request(rid=i, prompt=tokens.integers(0, vocab, int(p),
                                                  dtype=np.int32),
                    max_tokens=int(o), arrival=float(t))
            for i, (t, p, o) in enumerate(zip(due, plen, olen))]
