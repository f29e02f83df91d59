"""The per-layer metrics that read the program's own spans, and the
public per-step record they rest on: on the tests' tiny cell, on the
CPU, through the harness unedited."""

import json
import os
import shutil
import sys
import types

from bench import run
from repro.serving.obs.trace import TRACER

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPAN_METRICS = ("step_host_ms", "loop_host_ms", "uploads_per_step",
                "admit_page_blocked_share", "queue_wait_p90_ms.span")


def test_step_record_carries_what_the_recording_stepper_reads(
        tiny_root, jax_cache_restored):
    """The ``engine.step`` spans' records give every step's decoding
    lanes (position, node), chunks and counters, and every request's
    tokens, nodes and token times, as `RecordingStepper` notes them from
    the program's private fields."""
    from bench import traffic as traffic_lib
    seed = 2**31 + 99
    spec = run.cell_spec(run.load_benchmark(tiny_root), "tiny.chat",
                         tiny_root)
    cfg, _, stepper, server, _ = run.build(spec, seed, tiny_root)
    run.warm_up(stepper, server, spec["config"], cfg.vocab)
    traffic = traffic_lib.load("tiny.chat", spec["traffic"])
    requests = traffic_lib.make_requests(traffic, seed, 1.0, cfg.vocab)
    logs, steps = run.serve_window(stepper, server, requests, 60.0)
    spans = TRACER.named("engine.step")
    assert len(spans) == len(steps) > 0 and not TRACER.spans_dropped
    tokens: dict = {}
    for i, (span, st) in enumerate(zip(spans, steps)):
        assert st["t0"] <= span.t0 <= span.t1 <= st["t1"]
        rec = span.data["record"]
        # the recording stepper notes (context after the step, node)
        assert [(int(r[2]) + 1, int(r[3])) for r in rec.decode] == \
            st["decode"]
        assert sorted((int(r[2]), int(r[3]), bool(r[4]))
                      for r in rec.chunks) == sorted(st["chunks"])
        assert (span.data["seg_batch"], span.data["seg_policy"]) == \
            (st["seg_batch"], st["seg_policy"])
        for lane, rid, tok in rec.firsts:
            tokens.setdefault(int(rid), []).append((i, int(tok), -1))
        for lane, rid, _, node, tok in rec.decode:
            tokens.setdefault(int(rid), []).append((i, int(tok), int(node)))
    assert sorted(tokens) == [g.rid for g in logs]
    for g in logs:
        got = tokens[g.rid]
        assert [t for _, t, _ in got] == g.tokens, g.rid
        assert [n for _, _, n in got] == g.nodes, g.rid
        assert [steps[i]["t1"] for i, _, _ in got] == g.times, g.rid
    admits = {s.rid: s for s in TRACER.named("engine.admit")}
    releases = {s.rid: s for s in TRACER.named("pool.release")}
    for g in logs:
        assert admits[g.rid].t1 <= g.admitted
        assert releases[g.rid].t1 <= g.finished


def _root_with_span_metrics(tiny_root, tmp_path):
    """The tiny root, with BENCHMARK.json adding the five span metrics
    as the repo's BENCHMARK.json has them (every cell reports them)."""
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    for name in SPAN_METRICS:
        m = dict(entries[name])
        m.pop("workloads")
        bench["per_layer"].append(m)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(root)


def test_traced_run_reports_the_span_metrics(tiny_root, tmp_path,
                                             jax_cache_restored):
    root = _root_with_span_metrics(tiny_root, tmp_path)
    out = run.run_cell("tiny.chat", 2**31 + 5, 2.0, True, root=root,
                       allow_cpu=True)
    assert out["correct"] is True, out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(SPAN_METRICS) <= set(got)
    assert abs(got["queue_wait_p90_ms.span"]
               - got["queue_wait_p90_ms"]) < 1.0
    assert got["step_host_ms"] > 0 and got["loop_host_ms"] > 0
    # occupancy, sid, page table, write page and slot, at least
    assert got["uploads_per_step"] >= 5
    assert 0.0 <= got["admit_page_blocked_share"] <= 100.0
    names = {n for n, _ in out["breakdown"]["idle_gaps"]}
    assert names & {"engine.plan", "engine.dispatch", "engine.sync",
                    "server.iteration", "pool.prepare_step",
                    "engine.page_ops", "engine.step", "engine.admit",
                    "pool.release", "pool.admit", "pool.commit_prefix"}


def test_span_readers_read_nothing_without_the_program_spans(monkeypatch):
    """On a program without the process tracer, or after the ring
    dropped spans, each reader returns None and does not raise."""
    rec = {"traced_steps": [{"t0": 0.0, "t1": 1.0},
                            {"t0": 1.0, "t1": 2.0}],
           "requests": []}
    readers = [run.metric_reader(n, REPO) for n in SPAN_METRICS]
    monkeypatch.setattr(TRACER, "spans_dropped", 3)
    assert [r.read(rec) for r in readers] == [None] * len(readers)
    monkeypatch.setitem(sys.modules, "repro.serving.obs.trace",
                        types.ModuleType("repro.serving.obs.trace"))
    assert [r.read(rec) for r in readers] == [None] * len(readers)
