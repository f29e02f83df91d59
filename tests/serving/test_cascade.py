"""Multi-model cascade serving tests (repro.serving.cascade,
DESIGN.md §10):

  * strategy layer: cross-model `edge_costs_cascade` semantics and the
    multi-model `Cascade` calibration,
  * host logic: `CascadeRouter` escalation/recall/commit lifecycle and
    `EscalationScheduler` FIFO lane discipline,
  * `CascadeSimStepper`: completion + per-model accounting, the
    DUAL-MODEL DECISION-PARITY gate vs `strategy.evaluate` (escalated
    lanes must decide exactly what the offline fold decides), TTFT
    counted at actual emission, determinism, re-pin credit, and the
    recall-beats-no-recall acceptance claims (`benchmarks/
    cascade_smoke.check` on the bench's own sweep),
  * `CascadeEngineStepper` on REAL smoke models: both models live in
    one process, bit-identical streams run-to-run, never-escalating
    cascades match single-model serving exactly, forced escalation
    exercises handoff + catch-up + de-escalation + prefix re-pin.
"""

import numpy as np
import pytest

from repro import strategy
from repro.core import traces
from repro.core.skip_dp import (edge_costs_cascade, edge_costs_cumulative)
from repro.serving import runtime as rt
from repro.serving.cascade import (CascadeRouter, CascadeSimStepper,
                                   EscalationScheduler, ModelBank,
                                   ModelSpec)
from repro.serving.runtime.request import Request

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


# --------------------------------------------------------------------------
# strategy layer: cross-model edge costs + multi-model calibration
# --------------------------------------------------------------------------

def test_edge_costs_cascade_semantics():
    costs = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
    # one model == plain cumulative
    np.testing.assert_allclose(edge_costs_cascade(costs, (5,)),
                               edge_costs_cumulative(costs))
    c = edge_costs_cascade(costs, (2, 3), entry_costs=(0.0, 10.0))
    # within model 0: cumulative
    assert c[1, 2] == 2.0
    # within model 1 (nodes 2,3,4 local 0,1,2): cumulative inside
    assert c[3, 4] == 4.0 and c[3, 5] == 9.0
    # crossing into model 1 from anywhere pays its full ladder through
    # the target node plus the entry charge — never the source's tail
    for row in (0, 1, 2):
        assert c[row, 3] == 3.0 + 10.0
        assert c[row, 5] == 12.0 + 10.0
    with pytest.raises(ValueError, match="boundaries"):
        edge_costs_cascade(costs, (2, 2))


def test_multi_model_cascade_calibration_and_solve():
    rng = np.random.default_rng(0)
    losses, boundaries = traces.cascade_traces(
        rng, 1_500, [(2.0, 3.0), (6.0, 9.0, 12.0)], head_overthink=0.3)
    assert boundaries == (2, 3)
    casc = strategy.Cascade.from_model_traces(
        [losses[:, :2], losses[:, 2:]],
        [np.full(2, 0.2), np.full(3, 0.6)], k=8, lam=0.8, solve=False)
    assert casc.boundaries == (2, 3) and casc.n_models == 2
    assert [casc.node_model(i) for i in range(5)] == [0, 0, 1, 1, 1]
    strat = strategy.make("skip_recall", casc, mode="cascade")
    res = strategy.evaluate(strat, jnp.asarray(losses[:200]))
    assert res.served_node.shape == (200,)
    with pytest.raises(ValueError, match="boundaries"):
        strategy.Cascade.uniform(5).solve_skip("cascade")


# --------------------------------------------------------------------------
# router + escalation scheduler (pure host logic)
# --------------------------------------------------------------------------

def _bank(n_lanes_small=2, n_lanes_large=2):
    return ModelBank([
        ModelSpec("s", 2, n_lanes=n_lanes_small, seg_time=0.01),
        ModelSpec("l", 3, n_lanes=n_lanes_large, seg_time=0.04,
                  prefill_tok_time=0.01),
    ])


def test_bank_offsets_and_validation():
    bank = _bank()
    assert bank.n_total == 5
    assert bank.offset(1) == 2 and bank.node_range(1) == (2, 5)
    assert [bank.model_of(i) for i in range(5)] == [0, 0, 1, 1, 1]
    with pytest.raises(ValueError, match="duplicate"):
        ModelBank([ModelSpec("x", 2), ModelSpec("x", 3)])


def test_router_recall_lifecycle_and_repin_credit():
    bank = _bank()
    router = CascadeRouter(bank, 2, policy="recall", patience=2)
    router.admit(0, prompt_len=8)
    assert router.resident(0) == [0] and router.floor(0) == 0
    # escalation: catch-up must cover prompt + emitted positions
    assert router.escalation_targets(0, [0, 1]) == [1]
    assert router.catchup_need(0, 1, 8) == 8
    router.begin_escalation(0, [1], {"k": "handoff"})
    assert router.pending_handoff(0) == {"k": "handoff"}
    assert router.finish_escalation(0, 8) == []      # recall: no drops
    assert router.resident(0) == [0, 1]
    # two tokens ignoring the large rung -> patience de-escalates it
    assert router.note_emit(0, [0, 1], served_node=1, prompt_len=8) == []
    assert router.note_emit(0, [0], served_node=0, prompt_len=8) == []
    assert router.note_emit(0, [0], served_node=0, prompt_len=8) == [1]
    assert router.resident(0) == [0]
    # the released rung retains its REGISTERED chain (the catch-up the
    # prefix cache committed at escalation): a re-escalation catches up
    # only the delta past it (re-pin, not recompute)
    need = router.catchup_need(0, 1, 8)
    assert need == (8 + 3) - 8   # 3 emitted since the chain registered
    assert router.release(0) == [0]


def test_router_commit_policy_pins_floor_and_drops_source():
    bank = _bank()
    router = CascadeRouter(bank, 1, policy="commit", patience=4)
    router.admit(0, prompt_len=4)
    router.begin_escalation(0, [1], None)
    assert router.finish_escalation(0, 4) == [0]     # source dropped
    assert router.resident(0) == [1]
    assert router.floor(0) == bank.offset(1)
    # commit never de-escalates
    for _ in range(6):
        assert router.note_emit(0, [1], served_node=3, prompt_len=4) == []


def test_escalation_scheduler_fifo_and_release():
    bank = _bank(n_lanes_large=1)
    esc = EscalationScheduler(bank, chunk=8)
    lane = esc.request(0, 1)
    assert lane == 0 and esc.lane_of(0, 1) == 0 and esc.slot_of(1, 0) == 0
    assert esc.request(1, 1) is None          # pool exhausted: queued
    assert esc.request(2, 1) is None
    assert esc.grants() == []                 # nothing freed yet
    esc.release(0, 1)
    assert esc.grants() == [(1, 1, 0)]        # FIFO order
    esc.release(1, 1)
    esc.cancel(2)                             # slot 2 finished waiting
    assert esc.grants() == []
    assert esc.peak_in_use[1] == 1
    with pytest.raises(ValueError, match="no escalation pool"):
        esc.request(0, 0)


# --------------------------------------------------------------------------
# simulation stepper
# --------------------------------------------------------------------------

N0, N1 = 2, 3


@pytest.fixture(scope="module")
def sim_setup():
    rng = np.random.default_rng(3)
    losses, boundaries = traces.cascade_traces(
        rng, 3_000, [(2.0, 3.0), (5.0, 8.0, 12.0)], head_overthink=0.3)
    costs = np.concatenate([np.full(N0, 0.5 / N0), np.full(N1, 2.0 / N1)])
    casc = strategy.Cascade.from_traces(losses[:1_500], 0.1 * costs,
                                        k=10, lam=0.9,
                                        boundaries=boundaries)
    bank = ModelBank([
        ModelSpec("small", N0, n_lanes=3, seg_time=0.01,
                  prefill_tok_time=0.001),
        ModelSpec("large", N1, n_lanes=2, seg_time=0.04,
                  prefill_tok_time=0.004),
    ])
    return casc, bank, losses[1_500:]


def _sim_requests(n, seed=5, arrival_gap=0.05):
    rng = np.random.default_rng(seed)
    return [Request(rid=r, prompt=rng.integers(0, 512, 8, np.int32),
                    max_tokens=3 + r % 5, arrival=r * arrival_gap,
                    strategy="skip_recall")
            for r in range(n)]


def _sim_serve(casc, bank, bank_traces, requests, *, policy="recall",
               patience=3):
    def mk(name, lam):
        return strategy.make("skip_recall", casc, mode="cascade")

    strat_bank, sid_of = rt.build_bank(requests, mk,
                                       ("skip_recall", None))
    stepper = CascadeSimStepper(bank, strat_bank, bank_traces,
                                overhead=0.002, policy=policy,
                                patience=patience, chunk=16)
    server = rt.Server(stepper, rt.LaneScheduler(bank[0].n_lanes),
                       sid_of, slo=2.0)
    return server.serve(requests), stepper


def test_sim_cascade_completes_and_accounts(sim_setup):
    casc, bank, bank_traces = sim_setup
    requests = _sim_requests(12)
    metrics, stepper = _sim_serve(casc, bank, bank_traces, requests)
    s = metrics.summary(slo=2.0)
    assert s["completed"] == len(requests)
    assert s["tokens"] == sum(r.max_tokens for r in requests)
    cs = stepper.cascade_stats()
    # every emitted token is attributed to exactly one serving model
    assert sum(cs["tokens_served"]) == s["tokens"]
    assert cs["escalations"] > 0          # the ladder was exercised
    assert cs["mean_served_loss"] is not None


def test_sim_cascade_decision_parity_with_evaluate(sim_setup):
    """Satellite 6: escalated (dual-model) lanes' decisions must equal
    `strategy.evaluate` on the same combined trace rows — escalation
    timing, lane waits, and catch-up latency cannot change WHAT is
    served, only WHEN."""
    casc, bank, bank_traces = sim_setup
    requests = _sim_requests(10)
    metrics, stepper = _sim_serve(casc, bank, bank_traces, requests)
    assert stepper.stats.escalations > 0, "gate needs escalated lanes"
    strat = strategy.make("skip_recall", casc, mode="cascade")
    for rec in metrics.records.values():
        rows = np.stack([bank_traces[(rec.rid * 9973 + t)
                                     % len(bank_traces)]
                         for t in range(rec.n_tokens)])
        ref = strategy.evaluate(strat, jnp.asarray(rows))
        np.testing.assert_array_equal(
            np.asarray(rec.tokens), np.asarray(ref.served_node),
            err_msg=f"rid {rec.rid}")
        # deep-model nodes really got served somewhere
    served_deep = stepper.stats.tokens_served[1]
    assert served_deep > 0


def test_sim_cascade_deterministic_and_order_invariant(sim_setup):
    casc, bank, bank_traces = sim_setup
    base = _sim_requests(8)
    m1, _ = _sim_serve(casc, bank, bank_traces, base)
    m2, _ = _sim_serve(casc, bank, bank_traces, base)
    for r in base:
        assert m1.records[r.rid].tokens == m2.records[r.rid].tokens
    # reversed arrivals: decisions (rid, t)-keyed -> identical streams
    rev = [Request(rid=r.rid, prompt=r.prompt, max_tokens=r.max_tokens,
                   arrival=(len(base) - 1 - r.rid) * 0.05,
                   strategy=r.strategy) for r in base]
    m3, _ = _sim_serve(casc, bank, bank_traces, rev)
    for r in base:
        assert m1.records[r.rid].tokens == m3.records[r.rid].tokens


def test_sim_ttft_counted_at_actual_emission(sim_setup):
    """Satellite 6 (emit-mask accounting): a first token that must
    escalate emits ONLY after the catch-up lands, so its TTFT includes
    the escalation latency (the lane is occupied-but-silent, exactly
    like a chunked-prefill lane)."""
    casc, bank, bank_traces = sim_setup
    requests = _sim_requests(10)
    metrics, stepper = _sim_serve(casc, bank, bank_traces, requests)
    assert stepper.stats.escalations > 0
    for rec in metrics.records.values():
        assert rec.first_token is not None
        assert rec.first_token >= rec.admitted
        # tokens arrive one per request per step at most: n_tokens
        # emissions need at least n_tokens steps' worth of clock
        assert rec.finished >= rec.first_token


def test_sim_commit_policy_commits_and_rejects_jumping_strategies(
        sim_setup):
    casc, bank, bank_traces = sim_setup
    requests = _sim_requests(8)

    def mk(name, lam):
        return strategy.make("norecall_threshold", casc, threshold=0.2,
                             lam=1.0)

    strat_bank, sid_of = rt.build_bank(requests, mk, ("nr", None))
    stepper = CascadeSimStepper(bank, strat_bank, bank_traces,
                                overhead=0.002, policy="commit",
                                patience=3, chunk=16)
    server = rt.Server(stepper, rt.LaneScheduler(3), sid_of, slo=2.0)
    m = server.serve(requests)
    assert m.summary()["completed"] == len(requests)
    assert stepper.stats.commits > 0
    assert stepper.stats.deescalations == 0   # commits never retreat

    skip = strategy.make("skip_recall", casc, mode="cascade")
    with pytest.raises(ValueError, match="NEXT table"):
        CascadeSimStepper(bank, (skip,), bank_traces, policy="commit")


def test_sim_repin_credit_on_reescalation(sim_setup):
    """De-escalated rungs retain their registered catch-up chain: a
    re-escalation skips the retained positions (repin_tokens counts
    them), mirroring the engine's prefix-cache hit.  A mid-range
    threshold on the small head makes escalation flip per token, so
    escalate -> idle -> de-escalate -> re-escalate cycles are
    guaranteed."""
    _, bank, bank_traces = sim_setup
    strat = (strategy.ThresholdStrategy(
        5, np.asarray([0.0, 0.45, 0.0, 0.0, 2.0], np.float32),
        recall=True, lam=1.0),)
    requests = [Request(rid=r, prompt=np.zeros(8, np.int32),
                        max_tokens=12, arrival=r * 0.05)
                for r in range(6)]
    stepper = CascadeSimStepper(bank, strat, bank_traces,
                                overhead=0.002, policy="recall",
                                patience=2, chunk=16)
    server = rt.Server(stepper, rt.LaneScheduler(3), lambda r: 0,
                       slo=5.0)
    m = server.serve(requests)
    assert m.summary()["completed"] == len(requests)
    cs = stepper.cascade_stats()
    assert cs["deescalations"] > 0
    assert cs["repin_tokens"] > 0


def test_cascade_smoke_acceptance_claims():
    """The ISSUE acceptance gate on the bench's own sweep: recall
    Pareto-dominates (toleranced) small/large monoliths and the
    no-recall ladder, and strictly beats no-recall at the highest
    pre-wall rate (`benchmarks/cascade_smoke.check`)."""
    from benchmarks.bench_runtime import cascade_vs_monolith
    from benchmarks.cascade_smoke import DURATION, RATES, check
    rows = cascade_vs_monolith(rates=RATES, duration=DURATION)
    assert check(rows) == []


# --------------------------------------------------------------------------
# real-engine cascade (smoke models)
# --------------------------------------------------------------------------

PROMPT_LEN = 10
VOCAB = 256


def _numpy_params(defs, seed):
    """`models.param.materialize`'s init recipes with the normal draws
    taken from numpy's RNG: the de-escalation and wedge scenarios below
    hinge on random-init losses, which must not move with JAX's PRNG
    implementation."""
    import math

    from repro.models.param import ParamDef
    rng = np.random.default_rng(seed)

    def leaf(d):
        if d.init in ("zeros", "ones"):
            return jnp.full(d.shape, float(d.init == "ones"), jnp.float32)
        std = d.scale
        if d.init == "fan_in":
            std /= math.sqrt(max(d.shape[d.fan_axis] if d.shape else 1, 1))
        return jnp.asarray(std * rng.standard_normal(d.shape), jnp.float32)

    return jax.tree.map(leaf, defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


@pytest.fixture(scope="module")
def engine_bank():
    from repro.configs.common import dense_decoder
    from repro.models import model as M
    cfg_s = dense_decoder("casc-s", n_layers=2, d_model=64, n_heads=2,
                          n_kv_heads=2, head_dim=32, d_ff=128,
                          vocab=VOCAB, n_segments=2, act="gelu")
    cfg_l = dense_decoder("casc-l", n_layers=3, d_model=96, n_heads=2,
                          n_kv_heads=2, head_dim=48, d_ff=192,
                          vocab=VOCAB, n_segments=3, act="gelu")
    p_s = _numpy_params(M.model_defs(cfg_s), 0)
    p_l = _numpy_params(M.model_defs(cfg_l), 1)
    bank = ModelBank([
        ModelSpec("casc-s", 2, n_lanes=2, cfg=cfg_s, params=p_s),
        ModelSpec("casc-l", 3, n_lanes=1, cfg=cfg_l, params=p_l),
    ])
    return bank


def _engine_requests(n, seed=5):
    rng = np.random.default_rng(seed)
    return [Request(rid=r,
                    prompt=rng.integers(0, VOCAB, PROMPT_LEN, np.int32),
                    max_tokens=2 + r % 3, arrival=r * 0.01)
            for r in range(n)]


def _engine_serve(bank, strat_bank, sid_of, requests, *,
                  policy="recall", patience=2, stepper=None,
                  pages=None):
    from repro.serving.cascade import CascadeEngineStepper
    if stepper is None:
        stepper = CascadeEngineStepper(
            bank, strat_bank, cache_len=32, prompt_len=PROMPT_LEN,
            page_size=8, chunk=4, policy=policy, patience=patience,
            pages=pages)
    server = rt.Server(stepper, rt.LaneScheduler(bank[0].n_lanes),
                       sid_of, slo=10.0)
    return server.serve(requests), stepper


def _threshold_bank(thresholds):
    """One recall-threshold strategy over the 5-node ladder with
    per-node thresholds — the knob that forces/forbids escalation."""
    thr = np.asarray(thresholds, np.float32)
    return (strategy.ThresholdStrategy(5, thr, recall=True, lam=1.0),)


def test_engine_cascade_bit_identical_across_runs(engine_bank):
    """The ISSUE acceptance: both models live in one process; token
    streams are bit-identical run-to-run for a fixed seed."""
    bank = engine_bank
    requests = _engine_requests(5)
    # unsatisfiable small thresholds -> every token escalates; large
    # node 1 always satisfies -> walk ends there; argmin serves
    strat_bank = _threshold_bank([0.0, 0.0, 0.0, 2.0, 2.0])
    m1, st1 = _engine_serve(bank, strat_bank, lambda r: 0, requests)
    assert m1.summary()["completed"] == len(requests)
    assert st1.stats.escalations > 0
    assert st1.stats.tokens_served[1] > 0
    m2, _ = _engine_serve(bank, strat_bank, lambda r: 0, requests)
    for r in requests:
        assert m1.records[r.rid].tokens == m2.records[r.rid].tokens, \
            f"request {r.rid} stream changed across runs"


def test_engine_cascade_no_escalation_matches_single_model(engine_bank):
    """A ladder whose strategy never leaves the small model must emit
    exactly what the single-model runtime emits — pins the walk_io
    handoff plumbing as a no-op when unused."""
    from repro.serving.runtime.scheduler import EngineStepper
    bank = engine_bank
    requests = _engine_requests(4, seed=9)
    # node-0 threshold trivially satisfied: stop at the first ramp
    strat_bank = _threshold_bank([2.0, 2.0, 2.0, 2.0, 2.0])
    m_casc, st = _engine_serve(bank, strat_bank, lambda r: 0, requests)
    assert st.stats.escalations == 0
    assert st.stats.tokens_served == [sum(r.max_tokens for r in requests),
                                      0]
    # equivalent single-model serving: same walk over the small model
    single = (strategy.ThresholdStrategy(2, np.full(2, 2.0, np.float32),
                                         recall=True, lam=1.0),)
    sm = bank[0]
    stepper = EngineStepper(sm.params, sm.cfg, single, n_lanes=2,
                            cache_len=32, prompt_len=PROMPT_LEN,
                            kv="paged", page_size=8, prefill_chunk=4)
    server = rt.Server(stepper, rt.LaneScheduler(2), lambda r: 0,
                       slo=10.0)
    m_single = server.serve(requests)
    for r in requests:
        assert m_casc.records[r.rid].tokens == \
            m_single.records[r.rid].tokens, f"request {r.rid}"


class _MantissaAlternator(strategy.ThresholdStrategy):
    """Escalate past the small head iff the head loss's mantissa is odd
    — a deterministic, data-dependent alternator (random-init models
    emit near-uniform losses, so both branches occur), which forces
    escalate -> idle -> de-escalate -> RE-escalate cycles."""

    def observe(self, state, node, losses, active, aux=None):
        state, cont = super().observe(state, node, losses, active, aux)
        esc = (jnp.floor(losses * 997.0).astype(jnp.int32) % 2) == 1
        cont = jnp.where(jnp.asarray(node) == 1, active & esc, cont)
        return state, cont


def test_engine_cascade_deescalation_and_prefix_repin(engine_bank):
    """Recall policy: rungs idle past the patience window release their
    lane; a later RE-escalation's catch-up hits the rung's prefix
    cache (re-pin) instead of recomputing the whole stream."""
    bank = engine_bank
    rng = np.random.default_rng(2)
    requests = [Request(rid=0,
                        prompt=rng.integers(0, VOCAB, PROMPT_LEN,
                                            np.int32),
                        max_tokens=14)]
    strat_bank = (_MantissaAlternator(
        5, np.asarray([0.0, 0.0, 0.0, 2.0, 2.0], np.float32),
        recall=True, lam=1.0),)
    # the thrashing residency keeps several catch-up chains warm, so
    # the large rung needs headroom beyond the 1-lane default pool
    m, st = _engine_serve(bank, strat_bank, lambda r: 0, requests,
                          patience=1, pages=[9, 13])
    assert m.summary()["completed"] == 1
    cs = st.cascade_stats()
    assert cs["escalations"] >= 2, cs
    assert cs["deescalations"] >= 1, cs
    # the re-escalation skipped retained context via the prefix cache
    assert cs["repin_tokens"] > 0, cs
    assert cs["pools"]["casc-l"]["prefix_hits"] > 0, cs


def test_engine_cascade_wedge_raises_instead_of_spinning(engine_bank):
    """A deeper rung whose pool can never admit the catch-up must fail
    loudly (PoolExhausted) — not spin the serve loop forever."""
    from repro.serving.kvpool import PoolExhausted
    bank = engine_bank
    rng = np.random.default_rng(2)
    requests = [Request(rid=0,
                        prompt=rng.integers(0, VOCAB, PROMPT_LEN,
                                            np.int32),
                        max_tokens=14)]
    strat_bank = (_MantissaAlternator(
        5, np.asarray([0.0, 0.0, 0.0, 2.0, 2.0], np.float32),
        recall=True, lam=1.0),)
    with pytest.raises(PoolExhausted, match="wedged|cannot fit"):
        # default 1-lane large pool (5 pages): the re-escalating stream
        # plus its warm chains exceed what the pool can ever free
        _engine_serve(bank, strat_bank, lambda r: 0, requests,
                      patience=1, pages=[9, 5])


def test_engine_cascade_commit_policy_releases_source(engine_bank):
    bank = engine_bank
    requests = _engine_requests(3, seed=13)
    strat_bank = (strategy.ThresholdStrategy(
        5, np.asarray([0.0, 0.0, 0.0, 2.0, 2.0], np.float32),
        recall=False, lam=1.0),)
    m, st = _engine_serve(bank, strat_bank, lambda r: 0, requests,
                          policy="commit")
    assert m.summary()["completed"] == len(requests)
    assert st.stats.commits > 0
    # committed slots serve the large model only
    assert st.stats.tokens_served[0] == 0
    # and the small pool's pages were released at commit: only
    # prefix-cache-held prompt pages may remain
    assert st.steppers[0].pool.n_held.sum() == 0
