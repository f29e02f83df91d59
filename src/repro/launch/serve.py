"""Serving launcher: loads (or initializes) a checkpoint, calibrates a
`Cascade` from a calibration batch, builds the requested strategy from
the registry, and serves through the segment engine — either one batched
generation (default) or a continuous-batching traffic session
(``--server``):

  PYTHONPATH=src python -m repro.launch.serve --arch paper-ee-100m \
      --smoke --policy recall_index --lam 0.5 --tokens 32

  PYTHONPATH=src python -m repro.launch.serve --arch paper-ee-100m \
      --smoke --server --rate 8 --duration 5 --policy recall_index

``--policy`` accepts any online name from ``repro.strategy.available()``
— including the table-backed ``skip_recall`` and ``tree_index``
strategies (§5) that share the line calibration.  ``--server`` replays a
seeded open-loop workload (``--workload poisson|bursty|diurnal``) into
the lane scheduler and reports throughput, latency percentiles, goodput
under ``--slo-ms``, and segments saved (repro.serving.runtime).

``--cascade small:large`` serves a MULTI-MODEL ladder in one process
(repro.serving.cascade, DESIGN.md §10): the strategy's node line spans
every model, escalation chunk-prefills the stream onto deeper models,
and ``--escalate-policy recall`` makes revisiting an earlier model a
page-table re-pin:

  PYTHONPATH=src python -m repro.launch.serve --smoke --server \
      --cascade paper-ee-100m:paper-ee-100m --policy skip_recall \
      --rate 4 --duration 5 --lanes 4 --cascade-lanes 2

``--adaptive`` serves traffic under the CONTROL PLANE (DESIGN.md §11):
``--gears`` names a bank of lambda points, the `GearPlanner` solves
each into a provably-optimal recall strategy and prices its
sustainable rate, and the `AdaptiveController` switches gears from
live telemetry (with ``--recal-interval`` seconds between online
table re-fits — sim steppers only; the engine path gets gear
switching without recalibration):

  PYTHONPATH=src python -m repro.launch.serve --smoke --server \
      --adaptive --gears quality:0.95,balanced:0.92,turbo:0.75 \
      --workload diurnal --rate 8 --duration 10 --recal-interval 2.5

Every ``--server`` mode can be OBSERVED (repro.serving.obs, DESIGN.md
§12): ``--trace-out`` writes a Chrome/Perfetto trace of the request
lifecycle and every per-token decision, ``--metrics-out`` snapshots
the metrics registry the console report renders from,
``--flight-recorder DIR`` arms anomaly post-mortem bundles,
``--regret`` arms the decision-quality regret meter + Pareto frontier
(DESIGN.md §15), and ``--profile-dir`` captures a ``jax.profiler``
trace around the loop.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import strategy
from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.models.param import materialize
from repro.serving.engine import Engine
from repro.serving.obs import (FlightRecorder, InvariantLedger,
                               Observability)
from repro.serving.obs.export import (profiler_capture, write_events,
                                      write_trace)
from repro.serving.obs.lossmap import goodput_lossmap
from repro.serving.obs.report import ServeReport, segments_saved_line
from repro.training import checkpoint

# aliases kept for muscle memory from the previous CLI
ALIASES = {
    "recall": "recall_index",
    "threshold": "norecall_threshold",
    "none": "always_last",
}
# hindsight-only strategies (online=False in the registry) cannot serve
ONLINE = strategy.available(online_only=True)


def build_strategy(name: str, casc: strategy.Cascade, *, threshold: float,
                   patience: int, lam: float | None = None):
    """Registry dispatch with the per-family CLI knobs applied.

    ``lam`` is the per-request override the runtime routes through
    `Request.lam`; threshold/patience strategies compare raw
    1-confidence (their lam is pinned to 1.0), so a per-request lam
    there is a contradiction we refuse rather than silently drop.
    """
    if name in ("norecall_threshold", "recall_threshold",
                "norecall_patience"):
        if lam is not None:
            raise ValueError(
                f"{name} serves raw confidences (lam fixed at 1.0); "
                "per-request lam is not supported for this family — "
                "tune --threshold/--patience instead")
        if name == "norecall_patience":
            return strategy.make(name, casc, patience=patience, lam=1.0)
        return strategy.make(name, casc, threshold=threshold, lam=1.0)
    if name == "skip_recall":
        # edge-cost semantics by cascade shape: multi-model ladders pay
        # skip_free-style cross-model edges ("cascade"); a single model
        # pays cumulative backbone for skipped segments
        mode = "cascade" if casc.boundaries is not None else "cumulative"
        if lam is not None:
            return strategy.make(name, casc, mode=mode, lam=lam)
        return strategy.make(name, casc, mode=mode)
    if lam is not None:
        return strategy.make(name, casc, lam=lam)
    return strategy.make(name, casc)


def _build_obs(args, *, policy=None, boundaries=None, casc=None,
               ) -> Observability | None:
    """The observability plane (DESIGN.md §12/§13), built only when
    asked — a ``None`` obs keeps every producer guard dead and the
    serve loop byte-identical to the pre-observability path.

    ``--obs-dir DIR`` is the one-flag bundle: it defaults every sink
    the four separate flags name into DIR (trace.json, events.json,
    metrics.json, flight bundles) and additionally arms the
    `InvariantLedger` (audit contracts + ledger.json); explicit flags
    still win for their own sink.  ``--regret`` arms the decision-
    quality `RegretMeter` against the serve's calibrated `Cascade`
    (DESIGN.md §15) — another pure tracer listener, same discipline
    as the ledger.
    """
    if args.obs_dir:
        os.makedirs(args.obs_dir, exist_ok=True)
        args.trace_out = args.trace_out or \
            os.path.join(args.obs_dir, "trace.json")
        args.metrics_out = args.metrics_out or \
            os.path.join(args.obs_dir, "metrics.json")
        args.flight_recorder = args.flight_recorder or args.obs_dir
    if not (args.trace_out or args.metrics_out or args.flight_recorder
            or args.profile_dir or args.regret):
        return None
    flight = None
    if args.flight_recorder:
        os.makedirs(args.flight_recorder, exist_ok=True)
        flight = FlightRecorder(out_dir=args.flight_recorder)
    ledger = None
    if args.obs_dir:
        ledger = InvariantLedger(policy=policy, boundaries=boundaries,
                                 out_dir=args.obs_dir)
    regret = None
    if args.regret:
        from repro.serving.obs.regret import RegretMeter
        regret = RegretMeter(casc)
    return Observability(flight=flight, ledger=ledger, regret=regret,
                         profile_dir=args.profile_dir)


def _finish_obs(args, obs: Observability | None,
                report: ServeReport, *, faults=None) -> None:
    """Render the report, then the sinks: trace stats fold into the
    report first (so they land in the metrics snapshot too), then the
    Perfetto trace and the registry snapshot, if asked for.  A
    `FaultPlan` the serve ran under is embedded in the trace/events
    artifacts (``faults/v1``) so replay reproduces the chaos."""
    if obs is not None:
        report.add_trace(obs.tracer, obs.flight)
        if obs.ledger is not None:
            report.add_ledger(obs.ledger.report())
        # always rendered, even for an empty or overflowed ring — an
        # explicit zero (or a partial-ring map) over silence, so a
        # bundle consumer never has to guess whether the section was
        # clean or merely missing
        report.add_lossmap(goodput_lossmap(
            obs.tracer.events, slo=args.slo_ms / 1e3))
        if obs.regret is not None:
            # listeners see every emission — a ring overflow does not
            # taint the meter, so the report stays asserted
            report.add_regret(obs.regret.report())
            report.add_pareto(obs.regret.pareto.as_doc())
    report.print()
    if obs is not None and args.trace_out:
        write_trace(obs.tracer, args.trace_out, faults=faults,
                    regret=obs.regret)
        print(f"wrote Perfetto trace to {args.trace_out} "
              "(load in ui.perfetto.dev)")
    if args.metrics_out:
        report.registry.to_json(args.metrics_out)
        print(f"wrote metrics snapshot to {args.metrics_out}")
    if obs is not None and args.obs_dir:
        write_events(obs.tracer, os.path.join(args.obs_dir, "events.json"),
                     faults=faults)
        if obs.ledger is not None:
            with open(os.path.join(args.obs_dir, "ledger.json"), "w") as f:
                json.dump(obs.ledger.report(), f, indent=1, default=float)
        if obs.regret is not None:
            with open(os.path.join(args.obs_dir, "regret.json"), "w") as f:
                json.dump(obs.regret.report(), f, indent=1, default=float)
            with open(os.path.join(args.obs_dir, "pareto.json"), "w") as f:
                json.dump(obs.regret.pareto.as_doc(), f, indent=1,
                          default=float)
        print(f"wrote observability bundle to {args.obs_dir} "
              "(trace + events + metrics + ledger"
              + (" + regret + pareto" if obs.regret is not None else "")
              + ")")
    if obs is not None and obs.flight is not None and obs.flight.bundles:
        print(f"flight recorder: {len(obs.flight.bundles)} anomaly "
              f"bundle(s) in {args.flight_recorder}")


def _fault_plan(args, requests):
    """The fault plane's launch wiring (DESIGN.md §14): load the
    ``--faults`` chaos script and/or draw seeded per-request faults
    from ``--deadline-ms`` / ``--cancel-rate``, then stamp the
    request-borne faults onto the workload.  Returns ``(plan,
    stamped_requests)``; ``(None, requests)`` when no fault flag is
    set, keeping the default serve path byte-identical."""
    from repro.serving.faults import FaultPlan
    plan = None
    if args.faults:
        plan = FaultPlan.load(args.faults)
    if args.cancel_rate or args.deadline_ms is not None:
        gen = FaultPlan.generate(
            requests, seed=args.seed + 7, cancel_rate=args.cancel_rate,
            deadline=(args.deadline_ms / 1e3
                      if args.deadline_ms is not None else None))
        if plan is None:
            plan = gen
        else:
            # a scripted plan wins per rid; flags fill the gaps
            gen.cancel_at.update(plan.cancel_at)
            gen.deadline.update(plan.deadline)
            plan.cancel_at, plan.deadline = gen.cancel_at, gen.deadline
    if plan is not None:
        requests = plan.stamp(requests)
    return plan, requests


def _governor(args, plan):
    """A `DegradeGovernor` when faults are active and not opted out."""
    if plan is None or args.no_governor:
        return None
    from repro.serving.faults import DegradeGovernor
    return DegradeGovernor()


def _set_reclaim(args, *pools) -> None:
    """Arm ``--kv-reclaim`` on every paged pool the stepper built."""
    if args.kv_reclaim is None:
        return
    if not 0.0 < args.kv_reclaim <= 1.0:
        raise SystemExit(f"--kv-reclaim {args.kv_reclaim} outside (0, 1]")
    for pool in pools:
        if pool is not None:
            pool.reclaim_watermark = float(args.kv_reclaim)


def _serve_batch(args, cfg, params, strat) -> None:
    """The original one-shot path: one fixed batch, prefill to done."""
    engine = Engine(params, cfg, strat, cache_len=args.cache_len)
    key = jax.random.PRNGKey(args.seed)
    prompts = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab)}
    t0 = time.time()
    stats = engine.generate(prompts, args.tokens)
    dt = time.time() - t0
    n_nodes = cfg.n_ramps + 1
    print(f"generated {args.batch}x{args.tokens} tokens in {dt:.2f}s "
          f"({args.batch * args.tokens / dt:.1f} tok/s)")
    print(segments_saved_line(stats.segments_run_batch,
                              stats.segments_run_policy,
                              steps=args.tokens, n_seg=len(cfg.segments),
                              lane_steps=args.tokens * args.batch))
    print(f"served-node histogram: "
          f"{np.bincount(stats.served_nodes.ravel(), minlength=n_nodes)}")


def _calibrate_multi(cfgs, params_list, key, lam, *, k: int = 16,
                     t: int = 128, seq: int = 32) -> strategy.Cascade:
    """Multi-model calibration: every ladder model prefills the SAME
    random prompts; the concatenated per-node losses become one
    `Cascade` with model boundaries (strategy/cascade.py), per-node
    costs weighted by each model's backbone FLOPs share."""
    toks = jax.random.randint(key, (t, seq), 0, cfgs[0].vocab)
    model_losses, weights = [], []
    for cfg, params in zip(cfgs, params_list):
        _, _, node_losses, _ = M.prefill(params, cfg, {"tokens": toks},
                                         cache_len=seq + 8)
        model_losses.append(np.asarray(node_losses))
        # FLOPs proxy: layers x d_model^2 (dense decode cost order)
        layers = sum(seg.n_layers for seg in cfg.segments)
        weights.append(layers * cfg.d_model ** 2)
    base = weights[0]
    model_costs = [
        (1.0 - lam) * np.full((ls.shape[1],),
                              (w / base) / ls.shape[1])
        for ls, w in zip(model_losses, weights)]
    return strategy.Cascade.from_model_traces(model_losses, model_costs,
                                              k=k, lam=lam, solve=False)


def _serve_cascade(args) -> None:
    """--cascade small:large — a ladder of models in ONE process,
    served as a T-Tamer multi-stage decision process
    (repro.serving.cascade, DESIGN.md §10)."""
    from repro.serving import runtime as rt
    from repro.serving.cascade import CascadeEngineStepper, ModelBank, \
        ModelSpec
    from repro.serving.runtime.workload import WorkloadSpec, make_workload

    arch_names = args.cascade.split(":")
    if len(arch_names) < 2:
        raise SystemExit("--cascade needs at least two ':'-separated "
                         "arch names (e.g. qwen3-4b:qwen3-14b)")
    cfgs = [get_config(a, smoke=args.smoke) for a in arch_names]
    vocabs = {cfg.vocab for cfg in cfgs}
    if len(vocabs) > 1:
        # fail BEFORE the expensive multi-model calibration: JAX clamps
        # out-of-range token ids silently, so a mismatched ladder would
        # burn minutes prefilling garbage before ModelBank errors
        raise SystemExit(
            f"--cascade models must share tokenization (one vocab); "
            f"got {sorted(vocabs)} for {arch_names}")
    key = jax.random.PRNGKey(0)
    params_list = []
    for i, cfg in enumerate(cfgs):
        params_list.append(materialize(M.model_defs(cfg),
                                       jax.random.PRNGKey(i)))
    ladder = " -> ".join(f"{a} ({cfg.n_ramps + 1} nodes)"
                         for a, cfg in zip(arch_names, cfgs))
    print(f"cascade ladder: {ladder} (random init demo — per-model "
          "checkpoints are a ROADMAP item)")

    name = ALIASES.get(args.policy, args.policy)
    if strategy.needs_tables(name):
        casc = _calibrate_multi(cfgs, params_list,
                                jax.random.PRNGKey(args.seed + 1),
                                args.lam)
    else:
        casc = strategy.Cascade.uniform(
            sum(cfg.n_ramps + 1 for cfg in cfgs), lam=args.lam,
            boundaries=tuple(cfg.n_ramps + 1 for cfg in cfgs))

    lanes = [args.lanes] + [args.cascade_lanes] * (len(cfgs) - 1)
    # rung-indexed spec names keep prefix caches isolated even when the
    # same arch appears twice (distinct params = distinct KV bytes)
    bank = ModelBank([
        ModelSpec(f"{i}:{a}", cfg.n_ramps + 1, n_lanes=n, cfg=cfg,
                  params=p)
        for i, (a, cfg, p, n) in enumerate(
            zip(arch_names, cfgs, params_list, lanes))])

    lo = max(1, min(4, args.tokens))
    spec = WorkloadSpec(rate=args.rate, duration=args.duration,
                        prompt_len=args.prompt_len, vocab=cfgs[0].vocab,
                        max_tokens=(lo, args.tokens), seed=args.seed,
                        strategy=name)
    requests = make_workload(args.workload, spec)
    if not requests:
        print("workload produced no arrivals; raise --rate or --duration")
        return

    def make_strategy(sname, lam):
        return build_strategy(sname, casc, threshold=args.threshold,
                              patience=args.patience, lam=lam)

    plan, requests = _fault_plan(args, requests)
    strat_bank, sid_of = rt.build_bank(requests, make_strategy,
                                       (name, None))
    stepper = CascadeEngineStepper(
        bank, strat_bank, cache_len=args.cache_len,
        prompt_len=args.prompt_len, page_size=args.page_size,
        chunk=args.prefill_chunk or 8,
        budgets=([args.prefill_budget] * len(cfgs)
                 if args.prefill_budget else None),
        pages=([args.pages] * len(cfgs) if args.pages else None),
        policy=args.escalate_policy, patience=args.escalate_patience,
        paged_kernel=args.paged_kernel,
        faults=plan, governor=_governor(args, plan))
    _set_reclaim(args, *(st.pool for st in stepper.steppers))
    slo = args.slo_ms / 1e3
    obs = _build_obs(args, policy=args.escalate_policy,
                     boundaries=casc.boundaries, casc=casc)
    server = rt.Server(stepper, rt.LaneScheduler(args.lanes), sid_of,
                       order=args.order, slo=slo, eos=args.eos, obs=obs,
                       enforce_deadlines=bool(plan and plan.deadline))
    print(f"serving {len(requests)} {args.workload} requests "
          f"(rate {args.rate}/s x {args.duration}s) on a "
          f"{'->'.join(arch_names)} cascade "
          f"({'+'.join(str(n) for n in lanes)} lanes), policy {name}, "
          f"escalate-policy {args.escalate_policy} "
          f"(patience {args.escalate_patience}), "
          f"SLO ttft<={args.slo_ms:.0f}ms ...")
    with profiler_capture(args.profile_dir):
        metrics = server.serve(requests)
    cs = stepper.cascade_stats()
    report = ServeReport()
    report.add_runtime(metrics.summary(slo=slo), slo_ms=args.slo_ms)
    report.add_segments(metrics.seg_batch, metrics.seg_policy,
                        steps=metrics.steps, n_seg=bank.n_total,
                        lane_steps=metrics.lane_steps)
    report.add_cascade(cs)
    _finish_obs(args, obs, report, faults=plan)
    if args.json:
        extra = {"policy": name, "rate": args.rate, "lanes": args.lanes,
                 "cascade": args.cascade,
                 "escalate_policy": args.escalate_policy,
                 "cascade_stats": {k: v for k, v in cs.items()
                                   if k != "pools"} | {
                     "pools": {m: dict(p)
                               for m, p in cs["pools"].items()}}}
        metrics.to_json(args.json, slo=slo, extra=extra)
        print(f"wrote metrics JSON to {args.json}")


def parse_gears(text: str):
    """``--gears`` grammar: comma-separated ``name:lam`` pairs (a bare
    ``lam`` gets an auto name), e.g. ``quality:0.95,turbo:0.75``."""
    from repro.serving.control import GearSpec
    specs = []
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            gname, lam = part.split(":", 1)
        else:
            gname, lam = f"g{part}", part
        specs.append(GearSpec(gname.strip(), float(lam)))
    if not specs:
        raise SystemExit(f"--gears {text!r} names no gears")
    return tuple(specs)


def _build_adaptive(args, cfg, params, *, mean_tokens, slo):
    """The --adaptive control plane: calibrate gear traces off the real
    model, solve + price the bank, build the controller.  Capacity is
    priced in the SIM cost model's virtual units (probes per token at
    nominal segment time) — gear ORDER and the relative thresholds are
    what selection runs on."""
    from repro.serving.control import AdaptiveController, GearPlanner
    key = jax.random.PRNGKey(args.seed + 1)
    toks = jax.random.randint(key, (128, 32), 0, cfg.vocab)
    _, _, node_losses, _ = M.prefill(params, cfg, {"tokens": toks},
                                     cache_len=40)
    rows = np.asarray(node_losses, np.float64)
    n = rows.shape[1]
    planner = GearPlanner(rows, np.full(n, 1.0 / n), k=12,
                          seg_time=0.01, overhead=0.002,
                          n_lanes=args.lanes, mean_tokens=mean_tokens)
    gear_bank = planner.plan(parse_gears(args.gears))
    controller = AdaptiveController(
        gear_bank, span=max(2.0, args.duration / 5), slo=slo,
        recal_interval=args.recal_interval, planner=planner)
    print("gear bank (quality-first): " + ", ".join(
        f"{g.name}[slot {g.slot}] lam={g.spec.lam:g} "
        f"work={g.work:.2f} max_rate={g.max_rate:.1f}/s"
        for g in gear_bank))
    return gear_bank, controller


def _serve_traffic(args, cfg, params, casc) -> None:
    """--server: continuous batching over an open-loop workload."""
    from repro.serving import runtime as rt
    from repro.serving.runtime.workload import WorkloadSpec, make_workload

    name = ALIASES.get(args.policy, args.policy)
    lo = max(1, min(4, args.tokens))
    spec = WorkloadSpec(rate=args.rate, duration=args.duration,
                        prompt_len=args.prompt_len, vocab=cfg.vocab,
                        max_tokens=(lo, args.tokens), seed=args.seed,
                        strategy=name)
    requests = make_workload(args.workload, spec)
    if not requests:
        print("workload produced no arrivals; raise --rate or --duration")
        return

    controller = None
    if args.adaptive:
        slo = args.slo_ms / 1e3
        gear_bank, controller = _build_adaptive(
            args, cfg, params, mean_tokens=(lo + args.tokens) / 2,
            slo=slo)
        bank, sid_of = gear_bank.strategies, controller.sid_of
        if args.recal_interval is not None:
            print("note: the engine stepper has no swappable array "
                  "bank — --adaptive serves gear SWITCHING here; "
                  "--recal-interval applies to sim steppers "
                  "(benchmarks.bench_runtime.adaptive_vs_frozen)")
    else:

        def make_strategy(sname, lam):
            return build_strategy(sname, casc, threshold=args.threshold,
                                  patience=args.patience, lam=lam)

        bank, sid_of = rt.build_bank(requests, make_strategy,
                                     (name, None))
    plan, requests = _fault_plan(args, requests)
    stepper = rt.EngineStepper(params, cfg, bank, n_lanes=args.lanes,
                               cache_len=args.cache_len,
                               prompt_len=args.prompt_len,
                               kv=args.kv, page_size=args.page_size,
                               n_pages=args.pages,
                               paged_kernel=args.paged_kernel,
                               prefill_chunk=args.prefill_chunk,
                               prefill_budget=args.prefill_budget)
    if plan is not None:
        # single-model engine: request-borne faults plus page squeezes
        # (the Server reads the plan off the stepper each step)
        stepper.faults = plan
    _set_reclaim(args, stepper.pool)
    slo = args.slo_ms / 1e3
    obs = _build_obs(args, casc=casc)
    server = rt.Server(stepper, rt.LaneScheduler(args.lanes), sid_of,
                       order=args.order, slo=slo, eos=args.eos,
                       controller=controller, obs=obs,
                       enforce_deadlines=bool(plan and plan.deadline))
    kv_desc = args.kv if args.kv == "ring" else (
        f"paged ({stepper.pool.n_pages} pages x {args.page_size} tokens)")
    if args.prefill_chunk:
        kv_desc += (f", chunked prefill ({args.prefill_chunk}-token "
                    f"chunks, {stepper.planner.budget} tokens/step)")
    policy_desc = (f"adaptive gears ({args.gears})" if controller
                   else f"policy {name}")
    print(f"serving {len(requests)} {args.workload} requests "
          f"(rate {args.rate}/s x {args.duration}s) on {args.lanes} lanes, "
          f"{policy_desc}, kv {kv_desc}, "
          f"SLO ttft<={args.slo_ms:.0f}ms ...")
    with profiler_capture(args.profile_dir):
        metrics = server.serve(requests)
    report = ServeReport()
    report.add_runtime(metrics.summary(slo=slo), slo_ms=args.slo_ms)
    if controller is not None:
        report.add_adaptive(controller.stats())
    report.add_segments(metrics.seg_batch, metrics.seg_policy,
                        steps=metrics.steps, n_seg=len(cfg.segments),
                        lane_steps=metrics.lane_steps)
    pool_stats = None
    if stepper.pool is not None:
        pool_stats = stepper.pool.stats()
        report.add_pool(pool_stats)
    if args.prefill_chunk:
        report.add_chunked_prefill(stepper.chunk_stats)
    _finish_obs(args, obs, report, faults=plan)
    if args.json:
        extra = {"policy": name, "rate": args.rate, "lanes": args.lanes,
                 "kv": args.kv, "prefill_chunk": args.prefill_chunk}
        if controller is not None:
            extra["adaptive"] = controller.stats()
        if pool_stats is not None:
            extra["kv_pool"] = pool_stats
        if args.prefill_chunk:
            extra["chunked_prefill"] = stepper.chunk_stats
        metrics.to_json(args.json, slo=slo, extra=extra)
        print(f"wrote metrics JSON to {args.json}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-ee-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--policy", default="recall_index",
                    choices=sorted(set(ONLINE) | set(ALIASES)))
    ap.add_argument("--lam", type=float, default=0.5)
    ap.add_argument("--threshold", type=float, default=0.4)
    ap.add_argument("--patience", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    # --server traffic mode (repro.serving.runtime)
    ap.add_argument("--server", action="store_true",
                    help="serve an open-loop workload with continuous "
                         "batching instead of one fixed batch")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="mean arrivals/sec")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="arrival window in seconds")
    ap.add_argument("--slo-ms", type=float, default=1000.0,
                    help="TTFT SLO for goodput accounting")
    ap.add_argument("--lanes", type=int, default=None,
                    help="lane count (default: --batch)")
    ap.add_argument("--workload", default="poisson",
                    choices=("poisson", "bursty", "diurnal"))
    ap.add_argument("--order", default="fifo", choices=("fifo", "edf"))
    ap.add_argument("--eos", type=int, default=None,
                    help="token id that ends a stream early (lane is "
                         "recycled immediately)")
    ap.add_argument("--kv", default="ring", choices=("ring", "paged"),
                    help="decode KV memory: per-lane ring caches or the "
                         "paged pool with shared-prefix reuse "
                         "(DESIGN.md §8)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--kv paged)")
    ap.add_argument("--pages", type=int, default=None,
                    help="total pool pages (--kv paged; default: "
                         "lanes x ceil(cache_len/page_size) + 1 — the "
                         "ring-equivalent HBM budget)")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="decode through the Pallas paged-attention "
                         "kernel (--kv paged; TPU hot path — on CPU it "
                         "runs in slow interpret mode)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="co-schedule admission prefill with decode in "
                         "chunks of this many prompt tokens instead of "
                         "stop-the-world batch-1 prefill programs "
                         "(--kv paged; DESIGN.md §9).  Also lifts the "
                         "fixed prompt bucket: any prompt that fits a "
                         "lane's pages is admissible")
    ap.add_argument("--cascade", default=None,
                    help="serve a MULTI-MODEL cascade: ':'-separated "
                         "arch names in escalation order (e.g. "
                         "qwen3-4b:qwen3-14b; shared tokenization "
                         "required).  All models live in one process; "
                         "the strategy decides per token which model "
                         "serves (repro.serving.cascade, DESIGN.md "
                         "§10).  Implies --server")
    ap.add_argument("--escalate-policy", default="recall",
                    choices=("recall", "commit"),
                    help="cascade residency policy: 'recall' retains "
                         "the source model (recall = page re-pin; "
                         "deeper rungs released after --escalate-"
                         "patience idle tokens), 'commit' pins the "
                         "stream to the escalated model for good")
    ap.add_argument("--escalate-patience", type=int, default=4,
                    help="recall policy: de-escalate a rung after this "
                         "many consecutive tokens that never probed it")
    ap.add_argument("--cascade-lanes", type=int, default=None,
                    help="decode lanes per deeper cascade rung "
                         "(default: max(1, --lanes // 2))")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="max prompt tokens prefilled per step across "
                         "all admitting lanes (default: --prefill-"
                         "chunk), split fairly over prompt-length "
                         "buckets")
    ap.add_argument("--adaptive", action="store_true",
                    help="serve under the adaptive control plane "
                         "(DESIGN.md §11): a gear bank of recall "
                         "strategies selected from live load "
                         "telemetry.  Implies --server")
    ap.add_argument("--gears",
                    default="quality:0.95,balanced:0.92,turbo:0.75",
                    help="the --adaptive gear bank: comma-separated "
                         "name:lam pairs (quality-first order is "
                         "derived from solved work, not list order)")
    ap.add_argument("--recal-interval", type=float, default=None,
                    help="seconds of serve time between online table "
                         "re-fits from observed outcomes (--adaptive; "
                         "sim steppers only — the engine path serves "
                         "gear switching without recalibration)")
    ap.add_argument("--json", default=None,
                    help="write runtime metrics JSON here")
    # observability plane (repro.serving.obs, DESIGN.md §12)
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace-event JSON of "
                         "the serve here (open in ui.perfetto.dev; "
                         "--server modes only)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics-registry snapshot JSON "
                         "here (every number the console report "
                         "shows, as labelled series)")
    ap.add_argument("--flight-recorder", default=None, metavar="DIR",
                    help="arm the anomaly flight recorder: post-mortem "
                         "bundles (triggering request's span history + "
                         "last events + metrics) land in DIR on TTFT-"
                         "SLO breach bursts, page exhaustion, stuck "
                         "escalation waiters, or gear thrash")
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="one-flag observability bundle: write the "
                         "Perfetto trace, the lossless obs_trace/v1 "
                         "event log, the metrics snapshot, flight "
                         "bundles, AND the invariant-ledger report "
                         "into DIR (arms the audit ledger; subsumes "
                         "--trace-out/--metrics-out/--flight-recorder, "
                         "which still win for their own sink)")
    ap.add_argument("--regret", action="store_true",
                    help="arm the decision-quality regret meter "
                         "(DESIGN.md §15): per-request regret against "
                         "the offline-optimal walk over the calibrated "
                         "tables, decomposed by cause, plus the "
                         "streaming accuracy-latency Pareto frontier.  "
                         "Report sections always; regret.json + "
                         "pareto.json under --obs-dir; a regret "
                         "counter track in --trace-out")
    ap.add_argument("--profile-dir", default=None,
                    help="jax.profiler logdir captured around the "
                         "serve loop (kernel-level attribution)")
    # fault plane (repro.serving.faults, DESIGN.md §14)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline budget from arrival: "
                         "expired requests are reaped mid-stream "
                         "(pages released, counted timed_out) and "
                         "escalations the deadline cannot afford are "
                         "denied by the degrade governor")
    ap.add_argument("--cancel-rate", type=float, default=0.0,
                    help="seeded per-request probability of a client "
                         "cancellation shortly after arrival (chaos "
                         "input; deterministic in --seed)")
    ap.add_argument("--faults", default=None, metavar="PLAN.json",
                    help="serve under a faults/v1 chaos script "
                         "(FaultPlan.save): scripted cancellations, "
                         "deadlines, rung-stall windows and KV page "
                         "squeezes")
    ap.add_argument("--kv-reclaim", type=float, default=None,
                    metavar="FRAC",
                    help="paged-KV occupancy watermark in (0,1]: above "
                         "it admission pressure clips attention history "
                         "off the longest lanes (sliding-window "
                         "reclamation) instead of refusing admission")
    ap.add_argument("--no-governor", action="store_true",
                    help="serve faults WITHOUT the degrade governor "
                         "(escalations park past their deadlines; the "
                         "chaos baseline the governor is gated against)")
    args = ap.parse_args()
    use_compile_cache()
    if args.lanes is None:
        args.lanes = args.batch
    if args.cascade_lanes is None:
        args.cascade_lanes = max(1, args.lanes // 2)
    if args.adaptive:
        args.server = True
        if args.cascade:
            raise SystemExit("--adaptive and --cascade are separate "
                             "serving modes; pick one")

    if args.cascade:
        _serve_cascade(args)
        return

    cfg = get_config(args.arch, smoke=args.smoke)
    key = jax.random.PRNGKey(0)
    if args.ckpt:
        state, _ = checkpoint.load(args.ckpt)
        params = jax.tree.map(jnp.asarray, state["params"])
        print(f"loaded checkpoint {args.ckpt}")
    else:
        params = materialize(M.model_defs(cfg), key)
        print("no checkpoint given — serving random init (demo mode)")

    name = ALIASES.get(args.policy, args.policy)
    if strategy.needs_tables(name):
        # table-backed strategies calibrate on real model traces; the
        # line/skip solves are triggered lazily inside make()
        casc = strategy.Cascade.calibrate(params, cfg, key, args.lam,
                                          solve=False)
    else:
        # topology/costs-only strategies skip the calibration prefill
        casc = strategy.Cascade.uniform(cfg.n_ramps + 1, lam=args.lam)
    strat = build_strategy(name, casc, threshold=args.threshold,
                           patience=args.patience)
    if casc.line_tables is not None:
        tables = casc.line_tables
        print(f"calibrated T-Tamer tables: n={tables.n} K={tables.k} "
              f"online-optimal value {float(tables.value):.4f}")
    print(f"strategy: {name} (registry: {', '.join(strategy.available())})")

    if args.server:
        _serve_traffic(args, cfg, params, casc)
    else:
        if args.kv != "ring":
            print("note: --kv paged applies to --server traffic mode; "
                  "the one-shot batch path always uses ring caches")
        if (args.trace_out or args.metrics_out or args.flight_recorder
                or args.obs_dir or args.regret):
            print("note: --trace-out/--metrics-out/--flight-recorder/"
                  "--obs-dir/--regret observe --server traffic "
                  "sessions; the one-shot batch path has no request "
                  "lifecycle to trace")
        _serve_batch(args, cfg, params, strat)


if __name__ == "__main__":
    main()
