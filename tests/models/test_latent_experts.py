"""The serving expert layer and the latent-attention chunk path:

  * the held shares of every rank, the shared experts counted once, add
    up to the uncut layer (the program's layer and the reference's);
  * a token's expert output is the same alone as among batch-mates and
    pad rows, and pad rows route to no expert; the expert counters, the
    computed rows counted in whole row tiles;
  * `moe_forward` honours ``norm_topk_prob`` (and the held share);
  * MLA chunked prefill into the latent pool equals `_mla_forward` over
    the same positions;
  * YaRN's frequencies and softmax scale for DeepSeek-V2's constants
    against a table computed by hand.
"""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as A
from repro.models.common import rope_cos_sin, yarn_freqs
from repro.models.config import MoEConfig
from repro.models.mlp import mlp_forward
from repro.models.moe import (_tile_rows, moe_defs, moe_forward,
                              moe_route, moe_serve, ragged_tile_rows)
from repro.models.param import materialize

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEY = jax.random.PRNGKey(0)
UNCUT = MoEConfig(num_experts=16, top_k=4, d_ff_expert=24, num_shared=1,
                  d_ff_shared=40, norm_topk_prob=False)


def _reference():
    path = os.path.join(REPO, "bench", "references", "deepseek_v2.py")
    spec = importlib.util.spec_from_file_location("ref_deepseek_v2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _share(p, cfg, rank, held):
    """Rank ``rank``'s parameters and config of the uncut layer ``p``:
    its experts ``rank * held ..`` first, the router's columns rotated
    to match (the layer holds experts 0 .. held - 1)."""
    lo = rank * held
    sp = dict(p, router=jnp.roll(p["router"], -lo, axis=1),
              **{n: p[n][lo:lo + held] for n in ("w_gate", "w_up", "w_down")})
    return sp, dataclasses.replace(cfg, held_experts=held)


def test_held_shares_add_up_to_the_uncut_layer():
    """Program: the rank outputs less the shared expert, summed, plus
    the shared expert once, equal the uncut `moe_serve`.  Reference:
    its `moe_layer` of each share the same way."""
    d, held = 32, 8
    p = materialize(moe_defs(UNCUT, d, "swiglu"), KEY)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, d))
    live = jnp.ones((40,), bool)
    whole, _ = moe_serve(p, x, UNCUT, "swiglu", live)
    shared = mlp_forward(p["shared"], x, "swiglu")
    parts, routed = [], 0
    for rank in range(UNCUT.num_experts // held):
        sp, scfg = _share(p, UNCUT, rank, held)
        y, cnt = moe_serve(sp, x, scfg, "swiglu", live)
        parts.append(y - shared)
        routed += int(cnt["expert_rows_live"])
    # every assignment lands on exactly one rank's share
    assert routed == 40 * UNCUT.top_k
    # float32 sums in another order: ~1e-6 of outputs of order 1
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5,
                               rtol=1e-5)

    ref = _reference()
    s = {"top_k": UNCUT.top_k, "norm_topk_prob": False,
         "routed_scaling_factor": 1.0}

    def ref_layer(experts, first):
        lw = {"router": p["router"], "w_gate": p["w_gate"][experts],
              "w_up": p["w_up"][experts], "w_down": p["w_down"][experts],
              **{"shared_" + n: p["shared"][n]
                 for n in ("w_gate", "w_up", "w_down")}}
        return ref.moe_layer(dict(s, experts_held=len(experts),
                                  experts_first=first), lw, x)

    with jax.default_matmul_precision("highest"):
        ref_whole = ref_layer(np.arange(16), 0)
        ref_parts = [ref_layer(np.arange(r * held, (r + 1) * held),
                               r * held) - shared for r in range(2)]
    np.testing.assert_allclose(sum(ref_parts) + shared, ref_whole,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(ref_whole, whole, atol=1e-5, rtol=1e-5)


def _tiles_touched(sizes, tile: int) -> int:
    """Row tiles that groups of ``sizes`` rows, laid end to end, touch,
    counted once per group."""
    start, n = 0, 0
    for size in sizes:
        n += len({r // tile for r in range(start, start + int(size))})
        start += int(size)
    return n


def test_expert_output_does_not_depend_on_batch_mates():
    """Row 0 alone, then among 23 other rows and 8 pad rows: the same
    output (float32 products over other row counts may sum in another
    order, ~1e-7; a dropped or re-routed assignment moves it by ~0.1).
    Pad rows route to no expert: the counters are those of the 24 live
    rows alone.  ``expert_rows`` counts the whole row tiles the grouped
    products compute, so it exceeds the live rows when tiles are
    part-filled and grows when more rows route."""
    cfg = get_config("deepseek-v2-lite-ep8", smoke=True)
    moe = cfg.segments[1].block.moe
    d = cfg.d_model
    p = materialize(moe_defs(moe, d, "swiglu"), KEY)
    x = jax.random.normal(jax.random.PRNGKey(2), (32, d))
    alone, c1 = moe_serve(p, x[:1], moe, "swiglu", jnp.ones((1,), bool))
    live = jnp.arange(32) < 24
    crowd, c2 = moe_serve(p, x, moe, "swiglu", live)
    np.testing.assert_allclose(crowd[0], alone[0], atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(crowd[24:], mlp_forward(p["shared"], x[24:],
                                                       "swiglu"),
                               atol=1e-6, rtol=1e-6)
    _, c24 = moe_serve(p, x[:24], moe, "swiglu", jnp.ones((24,), bool))
    _, c32 = moe_serve(p, x, moe, "swiglu", jnp.ones((32,), bool))
    assert {k: int(v) for k, v in c2.items()} == \
        {k: int(v) for k, v in c24.items()}

    chosen = np.asarray(moe_route(p, x, moe)[3])
    for rows, cnt in ((24, c2), (32, c32), (1, c1)):
        sizes = np.bincount(chosen[:rows].ravel(),
                            minlength=moe.num_experts)[:moe.n_held]
        assert int(cnt["expert_rows_live"]) == sizes.sum()
        assert int(cnt["expert_rows_max"]) == sizes.max()
        # lhs of rows x top-3 < 512 rows: 32-row tiles
        assert int(cnt["expert_rows"]) == 32 * _tiles_touched(sizes, 32)
    assert int(c2["expert_rows"]) > int(c2["expert_rows_live"]) > 0
    assert int(c32["expert_rows_live"]) > int(c2["expert_rows_live"])


@pytest.mark.parametrize("sizes,m,rows", [
    ([0] * 8, 12_288, 0),
    ([1] + [0] * 7, 12_288, 512),
    ([48] * 8, 12_288, 8 * 512),        # one tile, eight groups in it
    ([192] * 8, 12_288, 10 * 512),      # groups straddle tile ends
    ([512] * 8, 12_288, 8 * 512),
    ([768] * 8, 12_288, 16 * 512),
    ([1024] + [0] * 7, 12_288, 2 * 512),
    ([96] + [0] * 7, 96, 3 * 32),
    ([12] * 8, 96, 10 * 32),
])
def test_expert_rows_count_the_row_tiles_the_products_compute(sizes, m,
                                                              rows):
    """Whole row tiles, once per group that touches them: the pairs a
    TPU v5e's ragged dot was timed to compute at DeepSeek-V2-Lite's
    expert shapes, for the chunk sweep's lhs (12,288 rows) and a decode
    batch's (96)."""
    got = int(_tile_rows(jnp.asarray(sizes, jnp.int32), m))
    assert got == rows == ragged_tile_rows(m) * _tiles_touched(
        sizes, ragged_tile_rows(m))


def test_moe_forward_honours_norm_topk_prob_and_the_share():
    """The training dispatch (capacity high enough to drop nothing) with
    gates left as softmax values, over the held experts only, against
    looping tokens through them."""
    cfg = dataclasses.replace(UNCUT, capacity_factor=8.0, num_shared=0,
                              held_experts=8)
    d = 16
    p = materialize(moe_defs(cfg, d, "swiglu"), KEY)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (2, 6, d))
    y, _ = moe_forward(p, x, cfg, "swiglu")
    xf = np.asarray(x.reshape(-1, d), np.float64)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(xf @ np.asarray(
        p["router"], np.float64), jnp.float32), -1))
    want = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        for e in np.argsort(-probs[t])[:cfg.top_k]:
            if e >= 8:
                continue
            w = {n: np.asarray(p[n][e], np.float64)
                 for n in ("w_gate", "w_up", "w_down")}
            g = xf[t] @ w["w_gate"]
            h = g / (1 + np.exp(-g)) * (xf[t] @ w["w_up"])
            want[t] += probs[t, e] * (h @ w["w_down"])
    np.testing.assert_allclose(np.asarray(y).reshape(-1, d), want,
                               atol=1e-4, rtol=1e-4)
    renorm, _ = moe_forward(p, x, dataclasses.replace(
        cfg, norm_topk_prob=True), "swiglu")
    assert not np.allclose(np.asarray(renorm), np.asarray(y), atol=1e-3)


def test_mla_chunked_prefill_equals_the_full_forward():
    """A 20-token prompt through `attn_prefill_chunk` in chunks of 8, 8
    and 4 (two lanes, the second idle) against a float32 latent pool,
    equals `attn_forward` (the unchunked `_mla_forward`) at every
    position: absorbed against expanded attention sum in another order,
    ~1e-6 of outputs of order 1."""
    cfg = get_config("deepseek-v2-lite-ep8", smoke=True)
    attn = cfg.segments[0].block.attn
    d = cfg.d_model
    p = materialize(A.attn_defs(attn, d), KEY)
    n, ps, lanes, maxp, c = 20, 4, 2, 6, 8
    x = jax.random.normal(jax.random.PRNGKey(4), (1, n, d))
    full, _ = A.attn_forward(p, x, jnp.arange(n)[None], attn, 1e-6)

    pages = lanes * maxp + 1
    pool = {name: (jnp.full((1,) + shape, -1, dt) if name == "pos"
                   else jnp.zeros((1,) + shape, jnp.float32))
            for name, (shape, dt) in A.pool_cache_defs(
                attn, pages, ps).items()}
    table = np.zeros((lanes, maxp), np.int32)
    table[0, :5] = np.arange(1, 6)
    outs = []
    for start in range(0, n, c):
        w = min(c, n - start)
        pos = np.full((lanes, c), -1, np.int32)
        pos[0, :w] = np.arange(start, start + w)
        dest = np.zeros((lanes, c), np.int32)
        dest[0, :w] = table[0, pos[0, :w] // ps]
        slot = np.zeros((lanes, c), np.int32)
        slot[0, :w] = pos[0, :w] % ps
        xc = jnp.zeros((lanes, c, d)).at[0, :w].set(x[0, start:start + w])
        chunk = A.PrefillChunk(
            tok=jnp.zeros((lanes, c), jnp.int32), pos=jnp.asarray(pos),
            dest_page=jnp.asarray(dest), dest_slot=jnp.asarray(slot),
            start=jnp.asarray([start, 0], jnp.int32),
            last_idx=jnp.asarray([w - 1, 0], jnp.int32),
            emit=jnp.asarray([start + w == n, False]),
            active=jnp.asarray([True, False]))
        y, pool = A.attn_prefill_chunk(p, xc, pool, attn, 1e-6,
                                       jnp.asarray(table), chunk, 0)
        outs.append(y[0, :w])
        assert np.all(np.asarray(y[1]) == 0)           # idle lane skipped
    np.testing.assert_allclose(jnp.concatenate(outs), full[0], atol=1e-5,
                               rtol=1e-5)
    # the pool holds every position once, the garbage page none
    held = np.asarray(pool["pos"][0, 1:6, 0]).reshape(-1)
    assert sorted(held[held >= 0].tolist()) == list(range(n))
    assert np.all(np.asarray(pool["pos"][0, 0]) == -1)


def test_yarn_frequencies_and_scale_for_the_published_constants():
    """DeepSeek-V2-Lite: rope dims 64, theta 1e4, factor 40 over 4096
    positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim =
    0.707.  c(r) = 64 ln(4096 / (2 pi r)) / (2 ln 1e4): c(32) = 10.47,
    c(1) = 22.51, so dims 0-9 keep theta^(-i/32), dims 23-31 are divided
    by 40 and dims 10-22 blend on the ramp (i - 10) / 13.  The softmax
    scale is 192^-1/2 (1 + 0.0707 ln 40)^2 = 0.114721; cos and sin are
    not scaled (mscale / mscale_all_dim = 1)."""
    cfg = get_config("deepseek-v2-lite-ep8")
    attn = cfg.segments[0].block.attn
    table = {0: 1.0, 5: 10 ** -0.625, 9: 10 ** -1.125,
             10: 10 ** -1.25,
             16: 0.01 * (6 / 13) / 40 + 0.01 * (7 / 13),
             22: 10 ** -2.75 * (12 / 13) / 40 + 10 ** -2.75 / 13,
             23: 10 ** -2.875 / 40, 31: 10 ** -3.875 / 40}
    got = yarn_freqs(64, 10_000.0, attn.yarn)
    ref = _reference().yarn_inv_freq(64, 10_000.0, {
        "factor": 40.0, "original_max_position": 4096, "beta_fast": 32.0,
        "beta_slow": 1.0})
    for i, want in table.items():
        assert math.isclose(got[i], want, rel_tol=1e-12), i
        assert math.isclose(ref[i], want, rel_tol=1e-12), i
    assert math.isclose(attn.softmax_scale, 0.1147213868, rel_tol=1e-9)
    cos, sin = rope_cos_sin(jnp.asarray([3]), 64, 10_000.0, attn.yarn)
    np.testing.assert_allclose(np.asarray(cos[0]),
                               np.cos(3 * got).astype(np.float32),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin[0]) ** 2 + np.asarray(
        cos[0]) ** 2, 1.0, rtol=1e-6)
