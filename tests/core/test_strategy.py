"""Strategy registry tests: every registered strategy reproduces the
legacy (pre-refactor) `core.policies` decisions on shared synthetic
traces — pinned by golden digests generated from the originals at the
seed commit — the skip strategy matches the numpy reference walk, and
`observe` state threading survives jit / vmap / lax.scan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import strategy
from repro.core import skip_dp, traces
from repro.core.line_dp import solve_line
from repro.core.markov import MarkovChain
from repro.core.support import Support


def _sample_chain_np(rng, p0, trans, t):
    """(t, n) bin trajectories drawn with numpy's RNG, so the pinned
    traces do not move with JAX's PRNG implementation."""
    def draw(probs):                     # one categorical draw per row
        cum = np.cumsum(probs, axis=-1)
        u = rng.random(probs.shape[0]) * cum[:, -1]
        return np.minimum((u[:, None] >= cum).sum(-1), probs.shape[-1] - 1)
    bins = [draw(np.broadcast_to(p0, (t, p0.shape[0])))]
    for tr in trans:
        bins.append(draw(tr[bins[-1]]))
    return np.stack(bins, axis=1)


@pytest.fixture(scope="module")
def instance():
    rng = np.random.default_rng(7)
    n, k, t = 7, 12, 800
    p0, trans, costs, grid = traces.random_instance(rng, n, k)
    g = jnp.asarray(grid, jnp.float32)
    sup = Support(grid=g, edges=(g[1:] + g[:-1]) / 2)
    chain = MarkovChain(p0=jnp.asarray(p0, jnp.float32),
                        trans=jnp.asarray(trans, jnp.float32))
    cj = jnp.asarray(costs, jnp.float32)
    tables = solve_line(chain, cj, sup)
    bins = jnp.asarray(_sample_chain_np(np.random.default_rng(0),
                                        np.asarray(p0, np.float64),
                                        np.asarray(trans, np.float64), t))
    losses = g[bins]
    casc = strategy.Cascade(support=sup, chain=chain, costs=cj, lam=1.0,
                            line_tables=tables)
    return casc, tables, losses, bins, cj


# Golden digests of the PRE-REFACTOR core.policies implementations on the
# `instance` fixture traces (CPU f32): (weighted served_node checksum,
# weighted n_probed checksum, mean explore_cost, mean served_loss).  The
# wrappers are gone; these digests are the surviving pin of the
# legacy behaviour.  Re-pinned when the traces moved to numpy draws: the
# registry still reproduced every original digest on the original
# JAX-drawn traces (threefry_partitionable off), so only the inputs moved.
GOLDEN = {
    "recall_index": (189448, 579454, 0.135013, 0.295978),
    "norecall_threshold": (243607, 564007, 0.149759, 0.292267),
    "recall_threshold": (224843, 564007, 0.149759, 0.289257),
    "norecall_patience": (606621, 927021, 0.608784, 0.52629),
    "oracle": (272793, 593193, 0.139285, 0.262399),
    "oracle_norecall": (272793, 593193, 0.139285, 0.262399),
    "always_last": (922397, 242794, 0.75477, 0.523882),
    "always_first": (0, 320400, 0.095957, 0.477236),
}


def _digest(res):
    t = np.asarray(res.served_node).shape[0]
    w = np.arange(1, t + 1, dtype=np.int64)
    return (int(np.asarray(res.served_node, np.int64) @ w % 1_000_003),
            int(np.asarray(res.n_probed, np.int64) @ w % 1_000_003),
            round(float(np.asarray(res.explore_cost).mean()), 6),
            round(float(np.asarray(res.served_loss).mean()), 6))


@pytest.mark.parametrize("name", ["recall_index", "norecall_threshold",
                                  "recall_threshold", "norecall_patience",
                                  "oracle", "oracle_norecall",
                                  "always_last", "always_first"])
def test_registry_matches_legacy_policies(instance, name):
    casc, tables, losses, bins, cj = instance
    preds = jnp.asarray(np.asarray(bins) % 3)
    kwargs = {"norecall_threshold": {"threshold": 0.4},
              "recall_threshold": {"threshold": 0.4},
              "norecall_patience": {"patience": 2}}.get(name, {})
    strat = strategy.make(name, casc, **kwargs)
    res = strategy.evaluate(strat, losses, aux=preds)
    got = _digest(res)
    exp = GOLDEN[name]
    assert got[:2] == exp[:2], f"{name}: decision digest {got} != {exp}"
    assert got[2] == pytest.approx(exp[2], abs=2e-6), name
    assert got[3] == pytest.approx(exp[3], abs=2e-6), name


def test_registry_covers_all_legacy_policies():
    names = strategy.available()
    for legacy in ("recall_index", "norecall_threshold", "recall_threshold",
                   "norecall_patience", "oracle", "oracle_norecall",
                   "always_last", "always_first"):
        assert legacy in names
    # plus the table-backed variants that now reach serving
    assert "skip_recall" in names and "tree_index" in names


def test_make_unknown_name_raises(instance):
    casc = instance[0]
    with pytest.raises(KeyError, match="unknown strategy"):
        strategy.make("definitely_not_registered", casc)


def test_tree_index_matches_recall_index_objective(instance):
    """The exact sigma index and the binned if-stop table encode the same
    optimal policy (Def. 4.4) — objectives must agree tightly."""
    casc, _, losses, _, _ = instance
    r1 = strategy.evaluate(strategy.make("recall_index", casc), losses)
    r2 = strategy.evaluate(strategy.make("tree_index", casc), losses)
    assert float(r1.mean_total()) == pytest.approx(
        float(r2.mean_total()), rel=1e-3)


def test_skip_strategy_matches_reference_walk(instance):
    casc, _, losses, bins, cj = instance
    ec = skip_dp.edge_costs_skip_free(np.asarray(cj))
    st = skip_dp.solve_skip(casc.chain, ec, casc.support)
    strat = strategy.SkipRecallStrategy(st, casc.support, ec)
    res = strategy.evaluate(strat, losses)
    served, spent, probed = skip_dp.simulate_skip(
        st, np.asarray(losses), np.asarray(bins), ec)
    np.testing.assert_allclose(np.asarray(res.served_loss), served,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(res.explore_cost), spent,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(res.n_probed), probed.sum(1))


def test_cascade_solve_skip_modes(instance):
    casc, _, losses, _, _ = instance
    t_skip = casc.solve_skip("skip_free")
    assert casc.skip_mode == "skip_free"
    v_skip = float(t_skip.value)
    t_line = casc.solve_skip("cumulative")
    assert casc.skip_mode == "cumulative"
    # skipping can only help when intermediate costs are avoidable
    assert v_skip <= float(t_line.value) + 1e-6
    res = strategy.evaluate(
        strategy.make("skip_recall", casc, mode="skip_free"), losses)
    assert (np.asarray(res.n_probed) >= 1).all()


def test_evaluate_jit_and_vmap_state_threading(instance):
    """observe() threads pytree state through jit, vmap and lax.scan."""
    casc, _, losses, _, _ = instance
    strat = strategy.make("recall_index", casc)
    eager = strategy.evaluate(strat, losses)
    jitted = jax.jit(lambda l: strategy.evaluate(strat, l).served_node)
    np.testing.assert_array_equal(np.asarray(jitted(losses)),
                                  np.asarray(eager.served_node))
    stacked = jnp.stack([losses[:100], losses[100:200]])
    vmapped = jax.vmap(lambda l: strategy.evaluate(strat, l).served_node)
    out = vmapped(stacked)
    assert out.shape == (2, 100)
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.asarray(eager.served_node[:100]))


def test_evaluate_rejects_wrong_width(instance):
    casc, _, losses, _, _ = instance
    strat = strategy.make("recall_index", casc)
    with pytest.raises(ValueError, match="nodes"):
        strategy.evaluate(strat, losses[:, :3])


def test_deprecated_wrappers_removed():
    """PR 1 kept `core.policies` one release; this is that release."""
    with pytest.raises(ImportError):
        from repro.core import policies  # noqa: F401


def test_cascade_from_traces_end_to_end():
    rng = np.random.default_rng(1)
    losses, _, flops = traces.ee_like_traces(rng, 4_000, 6)
    lam = 0.6
    casc = strategy.Cascade.from_traces(losses[:2_000], (1 - lam) * flops,
                                        k=16, lam=lam)
    assert casc.n_nodes == 6
    ev = jnp.asarray(lam * losses[2_000:])
    best = strategy.evaluate(strategy.make("recall_index", casc, lam=1.0),
                             ev)
    thr = strategy.evaluate(
        strategy.make("norecall_threshold", casc, threshold=lam * 0.2,
                      lam=1.0), ev)
    # the DP-backed strategy optimizes the objective the baseline doesn't
    assert float(best.mean_total()) <= float(thr.mean_total()) + 1e-6
