"""Paged-attention decode kernel: interpret-mode execution vs the
pure-jnp oracle (kernels/ref.py), plus parity between the engine's
paged jnp gather path and the Pallas kernel inside a real decode layer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _pool_setup(rng, *, b, hkv, hd, n_pages, ps, maxp, lens,
                dtype=jnp.float32):
    """Random pools in the stored layout (k/v (P, Hkv, hd, ps), pos
    (P, 1, ps)) + per-lane sequential page tables for given lane
    lengths (len 0 = idle lane: garbage table, pos -1 everywhere)."""
    k_pages = jnp.asarray(rng.normal(size=(n_pages, hkv, hd, ps)) * 0.5,
                          dtype)
    v_pages = jnp.asarray(rng.normal(size=(n_pages, hkv, hd, ps)) * 0.5,
                          dtype)
    pos_pages = np.full((n_pages, 1, ps), -1, np.int32)
    table = np.zeros((b, maxp), np.int32)
    q_pos = np.zeros(b, np.int32)
    next_page = 1  # page 0 is the garbage sink
    for lane, n in enumerate(lens):
        if n == 0:
            continue
        q_pos[lane] = n - 1
        for j in range(-(-n // ps)):
            table[lane, j] = next_page
            lo = j * ps
            width = min(ps, n - lo)
            pos_pages[next_page, 0, :width] = np.arange(lo, lo + width)
            next_page += 1
    assert next_page <= n_pages
    return (k_pages, v_pages, jnp.asarray(pos_pages), jnp.asarray(table),
            jnp.asarray(q_pos))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,hd,ps,maxp,lens,window", [
    (2, 4, 2, 64, 8, 3, (20, 5), None),        # GQA, partial tail pages
    (3, 4, 4, 64, 8, 4, (32, 1, 17), None),    # MHA, full/min/odd lens
    (2, 8, 2, 80, 16, 2, (25, 0), None),       # odd hd, idle lane
    (2, 4, 2, 64, 8, 4, (30, 12), 10),         # sliding window
    (2, 12, 12, 64, 16, 3, (40, 7), None),     # paper-ee: group 1, hd 64
    (2, 16, 4, 64, 16, 3, (33, 16), None),     # granite: group 4, hd 64
])
def test_paged_attention_matches_ref(b, h, hkv, hd, ps, maxp, lens,
                                     window, dtype):
    rng = np.random.default_rng(b * h + hd)
    n_pages = 1 + sum(-(-n // ps) for n in lens)
    k_pages, v_pages, pos_pages, table, q_pos = _pool_setup(
        rng, b=b, hkv=hkv, hd=hd, n_pages=n_pages, ps=ps, maxp=maxp,
        lens=lens, dtype=dtype)
    q = jnp.asarray(rng.normal(size=(b, h, hd)) * 0.5, dtype)
    scale = 1.0 / np.sqrt(hd)
    out = ops.paged_attention(q, k_pages, v_pages, pos_pages, table,
                              q_pos, scale=scale, window=window,
                              interpret=True)
    n_used = jnp.minimum(q_pos // ps + 1, maxp)
    r = ref.paged_attention_ref(
        q.reshape(b, hkv, h // hkv, hd), k_pages, v_pages, pos_pages,
        table, q_pos, n_used, scale=scale, window=window).reshape(b, h, hd)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), atol=tol,
                               rtol=tol)


def test_paged_attention_idle_lane_returns_zeros():
    """A lane whose table is all garbage (pos -1) must come back exactly
    zero — the engine discards it via the occupancy mask, but NaNs would
    poison the shared batch."""
    rng = np.random.default_rng(0)
    k_pages, v_pages, pos_pages, table, q_pos = _pool_setup(
        rng, b=2, hkv=2, hd=64, n_pages=4, ps=8, maxp=2, lens=(10, 0))
    q = jnp.asarray(rng.normal(size=(2, 4, 64)), jnp.float32)
    out = ops.paged_attention(q, k_pages, v_pages, pos_pages, table,
                              q_pos, scale=0.125, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)


def test_paged_attention_ignores_future_and_stale_positions():
    """Slots holding positions beyond q_pos (stale shared-page tails)
    must not contribute: truncating a lane's q_pos must equal attention
    over only the prefix."""
    rng = np.random.default_rng(3)
    b, hkv, hd, ps = 1, 2, 64, 8
    k_pages, v_pages, pos_pages, table, q_pos = _pool_setup(
        rng, b=b, hkv=hkv, hd=hd, n_pages=4, ps=ps, maxp=3, lens=(20,))
    q = jnp.asarray(rng.normal(size=(b, 4, hd)), jnp.float32)
    # same pools, query pinned at position 11: entries 12..19 are
    # "future" relative to the query and must be masked out
    out_trunc = ops.paged_attention(q, k_pages, v_pages, pos_pages, table,
                                    jnp.asarray([11], jnp.int32),
                                    scale=0.125, interpret=True)
    # reference: pools physically truncated to 12 entries
    pos_cut = np.asarray(pos_pages).copy()
    pos_cut[pos_cut > 11] = -1
    out_ref = ops.paged_attention(q, k_pages, v_pages,
                                  jnp.asarray(pos_cut), table,
                                  jnp.asarray([11], jnp.int32),
                                  scale=0.125, interpret=True)
    np.testing.assert_allclose(np.asarray(out_trunc), np.asarray(out_ref),
                               atol=1e-6, rtol=1e-6)


def test_engine_stepper_paged_kernel_wiring():
    """EngineStepper(paged_kernel=True) really traces the Pallas kernel
    into the jitted token step (the contextvar is a trace-time choice)
    and serves the same tokens as the gather path."""
    import numpy as np
    from repro import strategy
    from repro.configs import get_config
    from repro.models import model as M
    from repro.models.param import materialize
    from repro.serving import runtime as rt
    from repro.serving.runtime.request import Request

    cfg = get_config("paper-ee-100m", smoke=True)
    params = materialize(M.model_defs(cfg), jax.random.PRNGKey(0))
    casc = strategy.Cascade.calibrate(params, cfg, jax.random.PRNGKey(1),
                                      lam=0.5, k=8, t=64, seq=16)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=0,
                    prompt=rng.integers(0, cfg.vocab, 12, dtype=np.int32),
                    max_tokens=3)]
    out = {}
    for use_kernel in (False, True):
        bank, sid_of = rt.build_bank(reqs, rt.cascade_factory(casc),
                                     ("recall_index", None))
        stepper = rt.EngineStepper(params, cfg, bank, n_lanes=1,
                                   cache_len=32, prompt_len=12,
                                   kv="paged", page_size=8,
                                   paged_kernel=use_kernel)
        server = rt.Server(stepper, rt.LaneScheduler(1), sid_of, slo=5.0)
        out[use_kernel] = server.serve(reqs).records[0].tokens
    assert out[True] == out[False]


def _ring_to_pools(ring, table, n_pages, ps):
    """Repack identity-layout ring caches (L, B, C, *row) into stacked
    page pools in the stored layout (L, P, *row or (1,), ps), lane i's
    pages at table[i]."""
    b, lane_pages = table.shape
    pools = []
    for seg_c in ring:
        attn = {}
        for name, leaf in seg_c["attn"].items():
            lf = np.asarray(leaf)
            row = lf.shape[3:] or (1,)
            pool = np.full((lf.shape[0], n_pages) + row + (ps,),
                           -1 if name == "pos" else 0, lf.dtype)
            packed = np.moveaxis(
                lf.reshape((lf.shape[0], b, lane_pages, ps) + row), 3, -1)
            for lane in range(b):
                pool[:, table[lane]] = packed[:, lane]
            attn[name] = jnp.asarray(pool)
        pools.append({"attn": attn})
    return pools


@pytest.mark.parametrize("g", [1, 4])
def test_gather_path_and_kernel_agree_on_one_pool(g):
    """The jnp gather path (paged_kernel off) and the Pallas kernel read
    the same stored pool — a layer-stacked (L, P, Hkv, hd, ps) pool at
    hd 64, a traced layer index, masked lanes — and return the same
    attention and the same written pool."""
    from repro.models import attention as A
    from repro.models.config import AttnConfig

    rng = np.random.default_rng(g)
    b, hkv, hd, ps, maxp, n_layers = 3, 2, 64, 8, 3, 2
    cfg = AttnConfig(n_heads=g * hkv, n_kv_heads=hkv, head_dim=hd)
    d = g * hkv * hd
    p = {name: jnp.asarray(rng.normal(size=shape) * 0.05, jnp.float32)
         for name, shape in (("wq", (d, d)), ("wk", (d, hkv * hd)),
                             ("wv", (d, hkv * hd)), ("wo", (d, d)))}
    n_pages = 1 + b * maxp
    lens = (20, 9, 0)
    k0, v0, pos0, table, _ = _pool_setup(
        rng, b=b, hkv=hkv, hd=hd, n_pages=n_pages, ps=ps, maxp=maxp,
        lens=lens, dtype=jnp.bfloat16)
    pool = {"k": jnp.stack([k0, k0[::-1]]), "v": jnp.stack([v0, -v0]),
            "pos": jnp.stack([pos0] * n_layers)}
    pos = jnp.asarray([20, 9, 0], jnp.int32)     # the token after each
    tbl = np.asarray(table)
    wp = jnp.asarray([tbl[0, 20 // ps], tbl[1, 9 // ps], 0], jnp.int32)
    kv = A.PagedKV(table, wp, pos % ps)
    mask = jnp.asarray([True, True, False])
    x = jnp.asarray(rng.normal(size=(b, 1, d)), jnp.float32)

    @jax.jit
    def run(pool, layer):
        outs = {}
        for mode in (False, True):
            with A.paged_kernel(mode):
                outs[mode] = A.attn_decode(p, x, pool, pos, cfg, paged=kv,
                                           write_mask=mask, layer=layer)
        return outs

    outs = run(pool, jnp.int32(1))
    y_gather, pool_gather = outs[False]
    y_kernel, pool_kernel = outs[True]
    live = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(y_kernel)[live],
                               np.asarray(y_gather)[live], atol=2e-3,
                               rtol=2e-3)
    for name in pool:
        np.testing.assert_array_equal(np.asarray(pool_kernel[name]),
                                      np.asarray(pool_gather[name]))
    # layer 1 got the live lanes' rows; layer 0 and the masked lane's
    # (garbage) target kept their bytes
    got = np.asarray(pool_gather["pos"])
    assert got[1, wp[0], 0, 20 % ps] == 20 and got[1, wp[1], 0, 9 % ps] == 9
    np.testing.assert_array_equal(got[0], np.asarray(pos0))
    assert (got[:, 0] == -1).all()


def test_paged_kernel_inside_decode_matches_gather_path():
    """models/attention.py paged decode with the Pallas kernel enabled
    == the jnp page-gather path, on a real smoke-model decode step."""
    from repro.configs import get_config
    from repro.models import attention as A
    from repro.models import model as M
    from repro.models.param import materialize

    cfg = get_config("qwen3-4b", smoke=True)
    params = materialize(M.model_defs(cfg), KEY)
    b, s, ps, lane_pages = 2, 12, 4, 4
    n_pages = b * lane_pages + 1
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0, cfg.vocab)
    _, ring, _, pos = M.prefill(params, cfg, {"tokens": toks},
                                lane_pages * ps)
    table = np.zeros((b, lane_pages), np.int32)
    table[:] = np.arange(1, lane_pages + 1)[None, :] \
        + np.arange(b)[:, None] * lane_pages
    paged_caches = _ring_to_pools(ring, table, n_pages, ps)
    wp = jnp.asarray(table[:, -1])          # tail page of each lane
    ws = (pos % ps).astype(jnp.int32)
    kv = A.PagedKV(jnp.asarray(table), wp, ws)

    x = params["embed"]["table"][toks[:, -1]][:, None, :]
    outs = {}
    for mode in ("gather", "kernel"):
        h = x
        with A.paged_kernel(mode == "kernel"):
            for si in range(len(cfg.segments)):
                h, _, _, _ = M.decode_segment(params, cfg, si, h,
                                              paged_caches[si], pos,
                                              paged=kv)
        outs[mode], _ = M.ramp_readout(params, cfg, h[:, 0, :])
    np.testing.assert_allclose(np.asarray(outs["kernel"]),
                               np.asarray(outs["gather"]), atol=2e-2,
                               rtol=2e-2)
