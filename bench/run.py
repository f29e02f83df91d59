#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell (``BENCHMARK.json``'s
``workloads``) names a configuration, ``bench/configs/<config>.json``,
and a traffic mix, ``bench/traffic/<mix>.json``; the per-layer metrics
are ``bench/metrics/<metric>.py`` and the correctness limits of the cell
``bench/limits/<cell>.json``.  All are found by name, so a new cell,
mix, configuration or metric is new files and entries, not edits.

A run builds the configuration's model with weights drawn on the device
from the seed, the program's paged KV pool, chunked prefill and Pallas
kernels (`EngineStepper(kv="paged", paged_kernel=True, ...)`), warms up
every shape the window uses, then serves the mix open-loop through
`Server.serve` for ``--seconds`` (requests due in the window, then a
bounded drain) and prints, as the last line of standard output, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics
from a profiler trace of a few steady seconds), ``device`` and, last,
``checks``: each number the correctness check compared with its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """The run cannot be measured here."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(root: str, *parts: str) -> dict:
    path = os.path.join(root, "bench", *parts)
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(bench: dict, name: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = _json(root, "configs", cell["config"] + ".json")
    traffic = _json(root, "traffic", cell["traffic"] + ".json")
    limits = _json(root, "limits", name + ".json")

    def reports(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits,
            "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
            "per_layer": [m for m in bench["per_layer"] if reports(m)]}


def metric_reader(name: str, root: str = ROOT):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    return load_module(path, "bench_metric_" + name.replace(".", "_"))


# ---------------------------------------------------------------------------
# device and compile bookkeeping
# ---------------------------------------------------------------------------

def require_devices(chips: int, allow_cpu: bool = False):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoDevice(f"no TPU found (JAX platform is "
                       f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs


class CompileLog:
    """Backend compile (or persistent-cache read) seconds per program
    (as `chip_smoke.py` keeps them)."""

    def __init__(self):
        import jax
        self.secs: collections.Counter = collections.Counter()
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, secs, fun_name="?", **_):
        if event == _COMPILE_EVENT:
            self.secs[fun_name] += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_time)
        jax.monitoring.unregister_event_listener(self._on_event)

    def report(self, label: str) -> int:
        n = len(self.secs)
        log(f"compile [{label}]: {sum(self.secs.values()):.2f}s over {n} "
            f"programs, persistent cache {self.hits} hits / "
            f"{self.misses} misses")
        for name, secs in self.secs.most_common(4):
            log(f"  {secs:8.2f}s  {name}")
        self.secs.clear()
        self.hits = self.misses = 0
        return n


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment says: two checkouts never share
    one.  Every program is cached, however quick its compile."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# the model and the system under test
# ---------------------------------------------------------------------------

def program_config(config: dict, root: str = ROOT):
    """The program's ModelConfig for the configuration file, checked
    against the sizes the file states."""
    from repro.configs import get_config
    cfg = get_config(config["arch"], smoke=bool(config.get("smoke")))
    s = config["sizes"]
    seg0 = cfg.segments[0].block
    got = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
           "vocab": cfg.vocab, "n_heads": seg0.attn.n_heads,
           "n_kv_heads": seg0.attn.n_kv_heads,
           "head_dim": seg0.attn.head_dim, "d_ff": seg0.d_ff,
           "act": seg0.act, "n_segments": len(cfg.segments),
           "rope_theta": seg0.attn.rope_theta, "norm_eps": cfg.norm_eps,
           "tie_embeddings": cfg.tie_embeddings}
    ref = reference_module(config, root)
    want_sizes = ref.segment_sizes(s["n_layers"], s["n_segments"])
    bad = {k: (v, s[k]) for k, v in got.items() if v != s[k]}
    if [g.n_layers for g in cfg.segments] != want_sizes:
        bad["segments"] = ([g.n_layers for g in cfg.segments], want_sizes)
    if bad:
        raise ValueError(f"program config {config['arch']} departs from "
                         f"the benchmark's file: {bad}")
    return cfg


def reference_module(config: dict, root: str = ROOT):
    name = config.get("reference", "dense_decoder")
    return load_module(os.path.join(root, "bench", "references",
                                    name + ".py"), "bench_ref_" + name)


def make_params(config: dict, cfg, seed: int, root: str = ROOT):
    import jax
    import jax.numpy as jnp

    from repro.models import model as M
    from repro.models.param import abstract
    ref = reference_module(config, root)
    dtype = jnp.dtype(config["weight_dtype"])
    params = ref.program_params(config["sizes"], seed, dtype)
    want = abstract(M.model_defs(cfg), dtype)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("drawn weights do not match the program's "
                         "parameter tree")
    jax.block_until_ready(params)
    return params


def make_bank(traffic: dict, n_nodes: int, root: str = ROOT):
    """(strategy bank, sid_of, expected served nodes of rid's tokens)."""
    exits = traffic["exits"]
    if exits["policy"] == "always_last":
        from repro.strategy.line import FixedNodeStrategy
        bank = (FixedNodeStrategy(n_nodes, n_nodes - 1),)

        def expected(rid, n):
            return np.full(n, n_nodes - 1)
        return bank, (lambda r: 0), expected
    dp = load_module(os.path.join(root, "bench", "strategies",
                                  "depth_profile.py"), "bench_depth_profile")
    cont, members = exits["continue"], int(exits["members"])
    if len(cont) != n_nodes - 1:
        raise ValueError(f"depth profile has {len(cont)} continue shares, "
                         f"the model {n_nodes - 1} ramps")
    bank = dp.make_bank(cont, members)

    def expected(rid, n):
        return dp.expected_nodes(cont, rid % members, n)
    return bank, (lambda r: r.rid % members), expected


def build(spec: dict, seed: int, root: str = ROOT):
    """Weights, bank, recording stepper and server of one run."""
    from repro.serving import runtime as rt
    from bench.serve import RecordingStepper, require_program_fields
    config, traffic = spec["config"], spec["traffic"]
    cfg = program_config(config, root)
    params = make_params(config, cfg, seed, root)
    bank, sid_of, expected = make_bank(traffic, len(cfg.segments), root)
    stepper = RecordingStepper(
        params, cfg, bank,
        n_lanes=config["lanes"], cache_len=config["lane_capacity"],
        prompt_len=config["prefill_chunk"], kv="paged",
        page_size=config["page_size"], n_pages=config["n_pages"],
        paged_kernel=True, prefill_chunk=config["prefill_chunk"],
        prefill_budget=config["prefill_budget"])
    server = rt.Server(stepper, rt.LaneScheduler(config["lanes"]), sid_of)
    require_program_fields(stepper, server)
    return cfg, params, stepper, server, expected


def warm_up(stepper, server, config: dict, vocab: int) -> None:
    """Compile every program and eager shape the window runs: the step
    (chunk and decode branches), page ops and resets through the
    stepper's own warm-up and a short serve, and the per-step scatter
    of first-token positions for every count of lanes that can finish
    their prompt in one step."""
    import jax
    import jax.numpy as jnp

    from repro.serving.runtime.request import Request
    stepper.warmup()
    lanes, chunk = config["lanes"], config["prefill_chunk"]
    longest = config["lane_capacity"] - 2
    rng = np.random.default_rng(0)
    warm = [Request(rid=i, prompt=rng.integers(
        0, vocab, min(chunk + 1 + i, longest), dtype=np.int32),
        max_tokens=2, arrival=0.0) for i in range(lanes)]
    server.serve(warm, warmup=False)
    for k in range(1, lanes + 1):
        # the same calls as EngineStepper.step on k finishing lanes
        idx = jnp.asarray(list(range(k)), jnp.int32)
        jax.block_until_ready(stepper.pos.at[idx].set(idx))
    stepper.alloc()


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------

def serve_window(stepper, server, requests, close: float):
    """Serve ``requests`` open-loop from a fresh pool until they are
    done or the server's clock passes ``close``; returns the request
    logs (by rid) and the step records."""
    from bench.serve import WindowClosed
    stepper.logs, stepper.steps, stepper.lane_rid = {}, [], {}
    server.scheduler = type(server.scheduler)(server.scheduler.n_lanes)
    stepper.clock = server._now
    stepper.stop_at = close
    try:
        server.serve(requests, warmup=False)
    except WindowClosed:
        pass
    stepper.clock = None
    return sorted(stepper.logs.values(), key=lambda g: g.rid), stepper.steps


def pct(vals, q) -> float | None:
    return float(np.percentile(np.asarray(vals, np.float64), q)) \
        if len(vals) else None


def end_to_end(logs, seconds: float, close: float) -> dict:
    """Host-clock end-to-end numbers over the requests due in the window
    (`RuntimeMetrics`' arithmetic on the raw timestamps)."""
    due = [g for g in logs if g.due < seconds]
    ttft = [((g.first if g.first is not None else close) - g.due) * 1e3
            for g in due]
    gaps = [(b - a) * 1e3 for g in logs
            for a, b in zip(g.times, g.times[1:])]
    emitted = sum(1 for g in logs for t in g.times if t <= seconds)
    return {"ttft_p90_ms": pct(ttft, 90), "itl_p99_ms": pct(gaps, 99),
            "output_tok_s": emitted / seconds}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, allow_cpu: bool = False) -> dict:
    """One run; returns the result object (the contract's last line).
    ``allow_cpu`` skips the look for a chip (tests and rehearsals)."""
    spec = cell_spec(load_benchmark(root), name, root)
    devs = require_devices(int(spec["cell"]["chips"]), allow_cpu)
    compiles = CompileLog()
    try:
        return _run(spec, name, seed, seconds, trace, root, devs, compiles)
    finally:
        compiles.close()


def _run(spec, name, seed, seconds, trace, root, devs, compiles) -> dict:
    import jax

    from bench import check, peaks, traffic as traffic_lib
    cell, config, traffic_spec = (spec["cell"], spec["config"],
                                  spec["traffic"])
    dev = devs[0]
    peak = peaks.peak(dev.device_kind) if dev.platform == "tpu" else None
    cache = use_cache(root) if dev.platform == "tpu" else "off"
    log(f"device: {dev.platform} {dev.device_kind} (x{len(devs)}); "
        f"compile cache {cache}")
    traffic = traffic_lib.load(cell["traffic"], traffic_spec)

    cfg, params, stepper, server, expected = build(spec, seed, root)
    warm_up(stepper, server, config, cfg.vocab)
    requests = traffic_lib.make_requests(traffic, seed, seconds, cfg.vocab)
    saturate = traffic.arrival == "saturate"
    close = seconds if saturate else seconds + traffic.drain_s
    setup_s = time.perf_counter() - T_START
    compiles.report("set-up")
    log(f"set-up {setup_s:.3f}s; {len(requests)} requests "
        f"({traffic.arrival}), window {seconds}s, close at {close}s")

    tdir = os.path.join(root, ".bench_trace", f"{name}-{seed}")
    traced = {}
    if trace:
        # a few steady seconds from 40% of the window: at least 3 s and
        # until 3 steps with a prefill chunk and 3 without are traced
        # (the per-step metrics read both kinds), at most 10 s
        t_on = seconds * 0.4
        least, most = min(3.0, seconds * 0.3), min(10.0, seconds * 0.5)

        def hook(now):
            if "on" not in traced and now >= t_on:
                shutil.rmtree(tdir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # device and TraceMe spans
                jax.profiler.start_trace(tdir, profiler_options=opts)
                traced["on"] = now
            elif "on" in traced and "off" not in traced:
                kinds = [bool(s["chunks"]) for s in stepper.steps
                         if s["t0"] >= traced["on"]]
                both = min(sum(kinds), len(kinds) - sum(kinds)) >= 3
                if (now - traced["on"] >= least and both) \
                        or now - traced["on"] >= most:
                    jax.profiler.stop_trace()
                    traced["off"] = now
        stepper.on_step = hook
        stepper.annotate = jax.profiler.TraceAnnotation
    try:
        logs, steps = serve_window(stepper, server, requests, close)
    finally:
        if "on" in traced and "off" not in traced:
            jax.profiler.stop_trace()
            traced["off"] = float("inf")
    in_window = compiles.report("window")
    mem = dev.memory_stats() or {}
    peak_bytes = mem.get("peak_bytes_in_use")
    pool_stats = stepper.pool.stats()
    pool_bad = check.pool_violations(stepper.pool)
    n_lanes, seg_sizes = stepper.n_lanes, [s.n_layers for s in cfg.segments]
    del stepper, server, params
    gc.collect()

    hist = np.zeros(len(seg_sizes), np.int64)
    for g in logs:
        for n in g.nodes[1:]:
            hist[n] += 1
    log(f"served-node histogram (decoded tokens): {hist.tolist()}")
    lateness = generator_lateness(logs, steps)
    log(f"generator lateness: p50 {lateness[0]:.3f} ms, p99 "
        f"{lateness[1]:.3f} ms, max {lateness[2]:.3f} ms")
    log(f"compiles inside the window: {in_window}")

    # correctness, after the program's state is freed
    sizes = config["sizes"]
    ref = reference_module(config, root)
    picked = check.sample(logs, traffic.sample, seed)
    kv_dtype = jax.numpy.dtype(config["kv_dtype"])
    gap = None
    if picked:
        plain = ref.plain_params(sizes, seed,
                                 jax.numpy.dtype(config["weight_dtype"]))
        gap, _, n_cmp = check.logit_gaps(
            ref, plain, sizes, picked, seq=traffic.max_context,
            rows=int(traffic.output["max"]) + 1, kv_dtype=kv_dtype)
        del plain
        log(f"reference: {len(picked)} requests, {n_cmp} served tokens "
            f"compared")
    limits = spec["limits"]
    checks = {
        "logit_gap": {"value": gap, "limit": limits["logit_gap"]["limit"]},
        "served_node_mismatches": {
            "value": check.served_nodes(logs, expected),
            "limit": 0},
        "budget_mismatches": {"value": check.budgets(logs, cfg.vocab),
                              "limit": 0},
        "pool_violations": {"value": len(pool_bad), "limit": 0},
    }
    for msg in pool_bad[:5]:
        log(f"pool: {msg}")
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    if saturate:
        attempted = sum(1 for g in logs if g.admitted is not None)
        failed = 0
    else:
        attempted = len(requests)
        firsts = {g.rid for g in logs if g.first is not None}
        failed = sum(1 for r in requests if r.rid not in firsts)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": {}, "device": device}
    if not trace:
        e2e = end_to_end(logs, seconds, close)
        e2e["setup_s"] = setup_s
        for m in spec["end_to_end"]:
            if e2e.get(m["name"]) is not None:
                out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                             "unit": m["unit"]}
    else:
        from bench import trace_reduce
        events = trace_reduce.read(trace_reduce.find_xplane(tdir))
        for i, d in enumerate(events["devices"]):
            mods = collections.Counter(m[0].split("(")[0]
                                       for m in d["modules"])
            log(f"trace device {i}: {len(d['ops'])} ops, modules "
                f"{mods.most_common(6)}")
        red = trace_reduce.reduce(events)
        shutil.rmtree(tdir, ignore_errors=True)
        on, off = traced["on"], traced["off"]
        rec = {"requests": logs, "steps": steps, "window_s": seconds,
               "pool": pool_stats, "n_lanes": n_lanes, "sizes": sizes,
               "segment_sizes": seg_sizes, "config": config,
               "peak": peak, "trace": red,
               "traced_steps": [s for s in steps
                                if s["t0"] >= on and s["t1"] <= off]}
        log(f"trace: {red['n_steps']} steps over {red['window_s']:.3f}s, "
            f"{len(rec['traced_steps'])} recorded; busy "
            f"{red['busy_s']:.3f}s; kernels {red['kernels']}")
        notes = {}
        for m in spec["per_layer"]:
            mod = metric_reader(m["name"], root)
            val = mod.read(rec)
            if val is not None:
                out["metrics"][m["name"]] = {"value": val,
                                             "unit": m["unit"]}
                if hasattr(mod, "note"):
                    notes[m["name"]] = mod.note(rec)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
        if notes:
            out["notes"] = notes
            for k, v in notes.items():
                log(f"{k}: {v}")
    for k, v in out["metrics"].items():
        log(f"metric {k}: {v['value']!r} {v['unit']}")
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    out["checks"] = checks
    return out


def generator_lateness(logs, steps) -> tuple:
    """How late the server loop saw each request: from its due time to
    the first admission or step that started after it (ms)."""
    marks = np.sort(np.asarray([s["t0"] for s in steps]
                               + [g.admitted for g in logs
                                  if g.admitted is not None]))
    late = []
    for g in logs:
        i = np.searchsorted(marks, g.due)
        if i < len(marks):
            late.append((marks[i] - g.due) * 1e3)
    if not late:
        return (0.0, 0.0, 0.0)
    return (pct(late, 50), pct(late, 99), float(max(late)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log("bench: the program (src/repro) is not in this checkout")
        return 2
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoDevice as e:
        log(f"bench: {e}")
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
