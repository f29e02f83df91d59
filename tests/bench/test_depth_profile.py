"""bench/strategies/depth_profile.py: the depth each token runs is a
function of its request and token index, with the traffic's shares."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.strategies import depth_profile as dp

CONT = [0.6, 0.5, 0.4]          # four nodes


def test_hash_numpy_equals_device():
    m, t, n = np.meshgrid(np.arange(5), np.arange(300), np.arange(4))
    host = dp.hash_u24(m, t, n)
    dev = np.asarray(dp.hash_u24(jnp.asarray(m, jnp.int32),
                                 jnp.asarray(t, jnp.int32),
                                 jnp.asarray(n, jnp.int32), xp=jnp))
    assert (host == dev).all() and host.max() < (1 << 24)


def test_expected_nodes_follow_the_shares():
    nodes = np.concatenate([dp.expected_nodes(CONT, m, 4000)
                            for m in range(8)])
    reach = [(nodes >= i).mean() for i in range(4)]
    for i, p in enumerate(CONT):
        share = reach[i + 1] / reach[i]
        n = reach[i] * len(nodes)
        assert abs(share - p) < 4 * np.sqrt(p * (1 - p) / n)


def test_observe_matches_host_recomputation():
    strat = dp.DepthProfileStrategy(CONT, member=3)
    obs = jax.jit(strat.observe, static_argnums=1)
    state = strat.init(1)
    got = []
    for _ in range(50):
        active = jnp.ones((1,), bool)
        for node in range(4):
            state, active = obs(state, node, jnp.zeros(1), active)
        got.append(int(strat.serve(state)[0]))
    assert got == dp.expected_nodes(CONT, 3, 50).tolist()


@pytest.fixture(scope="module")
def served():
    """A four-node smoke model served twice through the program's paged,
    chunked stepper: arrivals in rid order, then in reverse."""
    from bench.run import serve_window
    from bench.serve import RecordingStepper
    from repro.configs.common import dense_decoder
    from repro.models import model as M
    from repro.models.param import materialize
    from repro.serving import runtime as rt

    cfg = dense_decoder("depth-smoke", n_layers=4, d_model=64, n_heads=2,
                        n_kv_heads=2, head_dim=32, d_ff=128, vocab=256,
                        n_segments=4, act="gelu")
    params = materialize(M.model_defs(cfg), jax.random.PRNGKey(0))
    members = 4
    stepper = RecordingStepper(
        params, cfg, dp.make_bank(CONT, members), n_lanes=3, cache_len=48,
        prompt_len=8, kv="paged", page_size=8, prefill_chunk=8,
        prefill_budget=16)
    server = rt.Server(stepper, rt.LaneScheduler(3),
                       lambda r: r.rid % members)
    stepper.warmup()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, int(n), dtype=np.int32)
               for n in rng.integers(3, 20, 12)]
    budgets = rng.integers(8, 24, 12)
    runs = []
    for order in (range(12), reversed(range(12))):
        reqs = [rt.Request(rid=i, prompt=prompts[i],
                           max_tokens=int(budgets[i]), arrival=0.001 * k)
                for k, i in enumerate(order)]
        logs, _ = serve_window(stepper, server, reqs, 600.0)
        runs.append({g.rid: g for g in logs})
    return runs, members


def test_streams_do_not_depend_on_admission_order(served):
    (a, b), _ = served
    assert sorted(a) == sorted(b) == list(range(12))
    for rid in a:
        assert a[rid].tokens == b[rid].tokens
        assert a[rid].nodes == b[rid].nodes


def test_served_nodes_are_the_profiles(served):
    (a, _), members = served
    draws = {}       # one draw per (member, token index): requests of a
    for rid, g in a.items():        # member share their decisions
        want = dp.expected_nodes(CONT, rid % members, len(g.nodes) - 1)
        assert g.nodes[1:] == want.tolist() and g.nodes[0] == -1
        for j, n in enumerate(g.nodes[1:]):
            draws[(rid % members, j)] = n
    hist = np.bincount(list(draws.values()), minlength=4)
    assert (hist > 0).all()      # exits at every depth, not all at 0
    reach = [hist[i:].sum() for i in range(4)]
    for i, p in enumerate(CONT):
        share = reach[i + 1] / reach[i]
        assert abs(share - p) < 4 * np.sqrt(p * (1 - p) / reach[i])
