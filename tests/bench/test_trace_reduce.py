"""bench/trace_reduce.py on a small trace recorded on the CPU
(tests/bench/data/cpu_trace.xplane.pb, made by record_trace.py there):
four annotated steps of a jitted matrix product and tanh, with host
sleeps between them."""

import os

import numpy as np
import pytest

from bench import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "cpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def events():
    return tr.read(TRACE)


def _busy_by_grid(ops, lo, hi):
    """Independent union: mark every covered microsecond."""
    grid = np.zeros(int((hi - lo) // 1000) + 2, bool)
    for _, a, b, _ in ops:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            grid[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    return grid.sum() * 1e-6


def test_reads_steps_ops_and_modules(events):
    assert len(events["devices"]) == 1
    dev = events["devices"][0]
    assert {o[0] for o in dev["ops"]} == {"dot_general.1", "wrapped_tanh"}
    assert [m[0] for m in dev["modules"]] == ["jit_step"] * 4
    assert sum(h[0] == "bench_step" for h in events["host"]) == 4


def test_busy_union_and_idle_share(events):
    red = tr.reduce(events)
    steps = sorted(h for h in events["host"] if h[0] == "bench_step")
    lo, hi = steps[0][1], steps[-1][2]
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert red["busy_s"] == pytest.approx(
        _busy_by_grid(events["devices"][0]["ops"], lo, hi), abs=2e-5)
    assert red["idle_share"] == pytest.approx(
        1 - red["busy_s"] / red["window_s"])
    # pinned from the recorded trace: three 4 ms sleeps leave it idle
    assert red["window_s"] == pytest.approx(0.020787933, rel=1e-6)
    assert red["busy_s"] == pytest.approx(0.007770401, rel=1e-6)
    assert red["idle_gaps"][0][0] == "$time sleep"
    assert red["idle_gaps"][0][1] > 0.004
    # instances sum under one name: dot_general.1 -> dot_general
    assert dict(red["device_ops"])["dot_general"] == pytest.approx(
        0.00733736, rel=1e-6)


def test_per_kernel_sums_and_steps(events):
    red = tr.reduce(events, kernels=("dot_general", "tanh"))
    ops = events["devices"][0]["ops"]
    for k in ("dot_general", "tanh"):
        want = sum(b - a for n, a, b, _ in ops if k in n) * 1e-9
        assert red["kernels"][k] == pytest.approx(want)
    assert red["kernels"]["dot_general"] == pytest.approx(0.00733736,
                                                          rel=1e-6)
    assert red["n_steps"] == 4
    mods = events["devices"][0]["modules"]
    assert red["step_device_s"] == pytest.approx(
        [(b - a) * 1e-9 for _, a, b, _ in mods])
    # the benchmark's own kernels are not in this trace: no time, not 0
    assert tr.reduce(events)["kernels"] == {}


def test_union_of_overlaps():
    assert tr.union_ns([(0, 10), (5, 12), (20, 25), (21, 22)]) == 17
    assert tr.union_ns([]) == 0
