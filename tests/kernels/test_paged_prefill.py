"""Paged-prefill chunk kernel: interpret-mode execution vs the pure-jnp
oracle (kernels/ref.py) — ragged final chunks, idle prefill slots,
mid-page chunk boundaries — plus parity between the model's chunk
gather path and the Pallas kernel inside a real prefill-chunk layer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _chunk_setup(rng, *, b, hkv, hd, ps, maxp, starts, widths, c,
                 dtype=jnp.float32):
    """Random pool in the stored layout (k/v (P, Hkv, hd, ps), pos
    (P, 1, ps)) + per-lane sequential history tables: lane ``i`` has
    history positions [0, starts[i]) committed to its pages and a chunk
    of ``widths[i]`` in-flight queries at [starts[i], starts[i] +
    widths[i]) (width 0 = idle prefill slot: all rows padded)."""
    n_pages = 1 + sum(-(-s // ps) for s in starts)
    k_pages = jnp.asarray(rng.normal(size=(n_pages, hkv, hd, ps)) * 0.5,
                          dtype)
    v_pages = jnp.asarray(rng.normal(size=(n_pages, hkv, hd, ps)) * 0.5,
                          dtype)
    pos_pages = np.full((n_pages, 1, ps), -1, np.int32)
    table = np.zeros((b, maxp), np.int32)
    nxt = 1
    for lane, s in enumerate(starts):
        for j in range(-(-s // ps)):
            table[lane, j] = nxt
            lo = j * ps
            w = min(ps, s - lo)
            pos_pages[nxt, 0, :w] = np.arange(lo, lo + w)
            # mid-page boundary: fill the tail-page remainder with STALE
            # positions >= start — entries the chunk itself would have
            # scattered before attending; the kernel must mask them
            if w < ps:
                pos_pages[nxt, 0, w:] = np.arange(s, s + ps - w)
            nxt += 1
    q_pos = np.full((b, c), -1, np.int32)
    for lane, (s, w) in enumerate(zip(starts, widths)):
        q_pos[lane, :w] = np.arange(s, s + w)
    return (k_pages, v_pages, jnp.asarray(pos_pages), jnp.asarray(table),
            jnp.asarray(q_pos), jnp.asarray(starts, np.int32))


def _run_both(rng, *, b, h, hkv, hd, ps, maxp, starts, widths, c, window,
              dtype):
    k_pages, v_pages, pos_pages, table, q_pos, chunk_start = _chunk_setup(
        rng, b=b, hkv=hkv, hd=hd, ps=ps, maxp=maxp, starts=starts,
        widths=widths, c=c, dtype=dtype)
    g = h // hkv
    q = jnp.asarray(rng.normal(size=(b, c, h, hd)) * 0.5, dtype)
    ck = jnp.asarray(rng.normal(size=(b, c, hkv, hd)) * 0.5, dtype)
    cv = jnp.asarray(rng.normal(size=(b, c, hkv, hd)) * 0.5, dtype)
    scale = 1.0 / np.sqrt(hd)
    out = ops.paged_prefill(q, k_pages, v_pages, pos_pages, table, q_pos,
                            chunk_start, ck, cv, q_pos, scale=scale,
                            window=window, interpret=True)
    n_hist = jnp.clip(-(-chunk_start // ps), 0, maxp)
    qr = q.reshape(b, c, hkv, g, hd).transpose(0, 2, 1, 3, 4)
    r = ref.paged_prefill_ref(
        qr, q_pos, k_pages, v_pages, pos_pages, table, chunk_start,
        n_hist, ck.transpose(0, 2, 1, 3), cv.transpose(0, 2, 1, 3),
        q_pos, scale=scale, window=window)
    r = np.asarray(r, np.float32).transpose(0, 2, 1, 3, 4).reshape(
        b, c, h, hd)
    return np.asarray(out, np.float32), r, np.asarray(q_pos)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,hd,ps,maxp,starts,widths,c,window", [
    # page-aligned history, full + ragged chunks
    (2, 4, 2, 64, 8, 4, (16, 8), (6, 3), 6, None),
    # MID-PAGE chunk boundary: history ends inside a page whose tail
    # holds stale future positions (the chunk's own pre-scattered slots)
    (2, 4, 2, 64, 8, 4, (17, 3), (5, 5), 5, None),
    # idle prefill slot (width 0) next to a zero-history chunk
    (3, 4, 4, 64, 8, 3, (12, 0, 0), (4, 0, 6), 6, None),
    # sliding window crossing the history/chunk seam
    (2, 8, 2, 80, 8, 4, (20, 9), (6, 4), 6, 10),
    # paper-ee: group 1 at hd 64, a chunk crossing a page
    (2, 12, 12, 64, 16, 3, (21, 16), (8, 5), 8, None),
    # granite: group 4 at hd 64
    (2, 16, 4, 64, 16, 3, (32, 7), (6, 6), 6, None),
])
def test_paged_prefill_matches_ref(b, h, hkv, hd, ps, maxp, starts,
                                   widths, c, window, dtype):
    rng = np.random.default_rng(b * h + hd + (window or 0))
    out, r, q_pos = _run_both(rng, b=b, h=h, hkv=hkv, hd=hd, ps=ps,
                              maxp=maxp, starts=starts, widths=widths,
                              c=c, window=window, dtype=dtype)
    valid = q_pos >= 0
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(out[valid], r[valid], atol=tol, rtol=tol)
    # padded rows (ragged tails, idle slots) come back exactly zero —
    # the engine discards them, but NaNs would poison the fused step
    if (~valid).any():
        np.testing.assert_array_equal(out[~valid], 0.0)
        assert np.isfinite(out).all()


def test_paged_prefill_history_clipped_at_chunk_start():
    """Pool entries at positions >= chunk_start (the chunk's own
    just-scattered keys, or stale COW tails) must NOT contribute: the
    kernel output must equal a run whose pool is physically truncated
    below the chunk start."""
    rng = np.random.default_rng(5)
    b, h, hkv, hd, ps, maxp, c = 1, 4, 2, 64, 8, 3, 4
    k_pages, v_pages, pos_pages, table, q_pos, chunk_start = _chunk_setup(
        rng, b=b, hkv=hkv, hd=hd, ps=ps, maxp=maxp, starts=(13,),
        widths=(4,), c=c)
    q = jnp.asarray(rng.normal(size=(b, c, h, hd)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(b, c, hkv, hd)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(b, c, hkv, hd)), jnp.float32)
    out = ops.paged_prefill(q, k_pages, v_pages, pos_pages, table, q_pos,
                            chunk_start, ck, cv, q_pos, scale=0.125,
                            interpret=True)
    pos_cut = np.asarray(pos_pages).copy()
    pos_cut[pos_cut >= 13] = -1
    out_ref = ops.paged_prefill(q, k_pages, v_pages, jnp.asarray(pos_cut),
                                table, q_pos, chunk_start, ck, cv, q_pos,
                                scale=0.125, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=1e-6, rtol=1e-6)


def test_prefill_chunk_kernel_matches_gather_in_model():
    """models/attention.attn_prefill_chunk with the Pallas kernel
    enabled == the jnp page-gather path, through a real smoke-model
    prefill-chunk segment sweep (history + in-flight seam included)."""
    from repro.configs import get_config
    from repro.models import attention as A
    from repro.models import model as M
    from repro.models.param import materialize

    cfg = get_config("paper-ee-100m", smoke=True)
    params = materialize(M.model_defs(cfg), KEY)
    b, ps, lane_pages, c = 2, 4, 4, 5
    n_pages = b * lane_pages + 1
    specs = M.paged_cache_specs(cfg, b, n_pages, ps)

    def mat(spec, key=None):
        if isinstance(spec, dict):
            return {k: mat(v, k) for k, v in spec.items()}
        shape, dtype = spec
        return (jnp.full(shape, -1, dtype) if key == "pos"
                else jnp.zeros(shape, dtype))

    caches = [mat(s) for s in specs]
    table = np.zeros((b, lane_pages), np.int32)
    table[:] = np.arange(1, lane_pages + 1)[None, :] \
        + np.arange(b)[:, None] * lane_pages
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, 2 * c), 0,
                              cfg.vocab)
    tok_idx = np.arange(2 * c, dtype=np.int32)
    dp_all = table[:, tok_idx // ps]
    ds_all = np.broadcast_to(tok_idx % ps, (b, 2 * c))
    outs = {}
    for mode in ("gather", "kernel"):
        cs = [jax.tree.map(lambda x: x, seg) for seg in caches]
        h_last = None
        with A.paged_kernel(mode == "kernel"):
            for start in (0, c):           # two chunks: seam exercised
                sl = slice(start, start + c)
                chunk = A.PrefillChunk(
                    tok=toks[:, sl],
                    pos=jnp.broadcast_to(jnp.arange(start, start + c,
                                                    dtype=jnp.int32),
                                         (b, c)),
                    dest_page=jnp.asarray(dp_all[:, sl]),
                    dest_slot=jnp.asarray(ds_all[:, sl]),
                    start=jnp.full((b,), start, jnp.int32),
                    last_idx=jnp.full((b,), c - 1, jnp.int32),
                    emit=jnp.ones((b,), bool),
                    active=jnp.ones((b,), bool))
                x = params["embed"]["table"][chunk.tok]
                for si in range(len(cfg.segments)):
                    x, cs[si], _ = M.prefill_chunk_segment(
                        params, cfg, si, x, cs[si], jnp.asarray(table),
                        chunk)
                h_last = x[:, -1, :]
        outs[mode], _ = M.ramp_readout(params, cfg, h_last)
    np.testing.assert_allclose(np.asarray(outs["kernel"]),
                               np.asarray(outs["gather"]), atol=2e-2,
                               rtol=2e-2)
