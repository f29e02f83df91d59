"""The harness: BENCHMARK.json keeps the contract's shape, every name
in it finds its file, and a cell, mix, configuration or per-layer
metric added as new files alone is found and run (here on the CPU, at
the tests' tiny size, with the look for a chip skipped)."""

import json
import os
import re

import pytest

from bench import run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return run.load_benchmark(REPO)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_shape(bench):
    assert set(bench) == KEYS
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["command"][:2] == ["python3", "bench/run.py"]
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"])
        assert _line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        names.add(c["name"])
    cells = {}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert _line(w["why"])
        cells[w["name"]] = w
    assert len(cells) == len(bench["workloads"])
    assert {w["config"] for w in cells.values()} == names
    e2e = {}
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert "setup_s" in e2e
    per_cell = {c: set() for c in cells}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"]) and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for c in m.get("workloads", cells):
            assert c in e2e[m["moves"]], (m["name"], c)
            per_cell[c].add(m["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in cells:
        reported = {n for n, ws in e2e.items() if c in ws}
        assert "setup_s" in reported and len(reported) >= 2
        assert per_cell[c]


def test_every_name_finds_its_files(bench):
    for w in bench["workloads"]:
        spec = run.cell_spec(bench, w["name"], REPO)
        assert spec["traffic"]["exits"]["policy"] in ("depth_profile",
                                                      "always_last")
        assert spec["limits"]["logit_gap"]["limit"] > 0
        for m in spec["per_layer"]:
            assert hasattr(run.metric_reader(m["name"], REPO), "read")


def test_program_config_matches_each_file(bench):
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            config = json.load(f)
        cfg = run.program_config(config, REPO)
        assert cfg.name == config["arch"]


def test_new_cell_mix_and_metric_are_files_only(tiny_root,
                                                jax_cache_restored):
    """The tiny cell, its configuration, mix, limits and the metric
    ``tokens_decoded`` exist only as files under tests/bench/data: the
    harness runs them unedited."""
    out = run.run_cell("tiny.chat", 2**31 + 12345, 1.0, False,
                       root=tiny_root, allow_cpu=True)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 8 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p99_ms",
                                   "output_tok_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    traced = run.run_cell("tiny.chat", 7, 2.0, True, root=tiny_root,
                          allow_cpu=True)
    assert traced["correct"] is True
    assert traced["metrics"]["tokens_decoded"]["value"] > 0
    assert 0 < traced["device"]["busy_s"] <= traced["device"]["window_s"]
    assert len(traced["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("mix", ["ee100m.chat", "granite2b.rag",
                                 "ee100m.batch"])
def test_every_seed_serves_the_same_schedule(mix):
    """Two seeds get the same due times, lengths and budgets in the same
    order; only the prompt token ids differ."""
    from bench import traffic as traffic_lib
    with open(os.path.join(REPO, "bench", "traffic", mix + ".json")) as f:
        traffic = traffic_lib.load(mix, json.load(f))
    a = traffic_lib.make_requests(traffic, 2**31 + 17, 40.0, 1000)
    b = traffic_lib.make_requests(traffic, 3, 40.0, 1000)
    assert [(r.arrival, len(r.prompt), r.max_tokens) for r in a] == \
        [(r.arrival, len(r.prompt), r.max_tokens) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    lens = [len(r.prompt) for r in a]
    assert lens != sorted(lens)           # stratified, not sorted


def test_a_changed_private_field_stops_the_run():
    """A program whose stepper no longer keeps the fields the recording
    stepper reads is refused with the field's name."""
    from types import SimpleNamespace

    import numpy as np

    from bench.serve import ProgramChanged, require_program_fields
    pool = SimpleNamespace(seq_len=np.zeros(4, np.int32))
    stepper = SimpleNamespace(_prefilling={}, pool=pool, n_lanes=4, pos=0)
    server = SimpleNamespace(_now=lambda: 0.0,
                             scheduler=SimpleNamespace(n_lanes=4))
    require_program_fields(stepper, server)
    del stepper._prefilling
    with pytest.raises(ProgramChanged, match="_prefilling"):
        require_program_fields(stepper, server)
