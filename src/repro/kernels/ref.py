"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Each function mirrors its kernel's EXACT contract (shapes, dtypes,
masking, accumulation order is allowed to differ within float tolerance).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["flash_attention_ref", "paged_attention_ref",
           "paged_prefill_ref", "bellman_backup_ref", "ssd_chunk_ref",
           "ramp_exit_ref"]


def flash_attention_ref(q, k, v, *, scale: float, causal: bool = True,
                        window: int | None = None):
    """q (B,H,S,hd), k/v (B,Hkv,S,hd), H = G*Hkv.  Returns (B,H,S,hd)."""
    b, h, s, hd = q.shape
    hkv = k.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, s, hd)
    logits = jnp.einsum("bkgsd,bktd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    pos = jnp.arange(s)
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= pos[None, :] <= pos[:, None]
    if window is not None:
        mask &= pos[None, :] > pos[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", w, v.astype(jnp.float32))
    return out.reshape(b, h, s, hd).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, pos_pages, page_table, q_pos,
                        n_used, *, scale: float, window: int | None = None):
    """Paged single-token decode attention (paged_attention.py contract).

    q (B, Hkv, G, hd); k/v_pages (P, Hkv, hd, ps) and pos_pages
    (P, 1, ps) i32 (-1 = empty slot) — the pool as stored (DESIGN.md
    §8); page_table (B, maxp) i32; q_pos (B,) i32; n_used (B,) i32 —
    table entries at index >= n_used are ignored.  Returns (B, Hkv, G,
    hd); lanes with nothing attendable return zeros.
    """
    b, hkv, g, hd = q.shape
    ps = k_pages.shape[-1]
    maxp = page_table.shape[1]
    k = k_pages[page_table].astype(jnp.float32)     # (B, maxp, Hkv, hd, ps)
    v = v_pages[page_table].astype(jnp.float32)
    kpos = pos_pages[page_table][:, :, 0]           # (B, maxp, ps)
    k = k.transpose(0, 2, 1, 4, 3).reshape(b, hkv, maxp * ps, hd)
    v = v.transpose(0, 2, 1, 4, 3).reshape(b, hkv, maxp * ps, hd)
    valid = (kpos >= 0) & (kpos <= q_pos[:, None, None])
    if window is not None:
        valid &= kpos > (q_pos[:, None, None] - window)
    valid &= jnp.arange(maxp)[None, :, None] < n_used[:, None, None]
    valid = valid.reshape(b, 1, 1, maxp * ps)
    logits = jnp.einsum("bkgd,bktd->bkgt", q.astype(jnp.float32),
                        k) * scale
    # -1e30 (not -inf) keeps all-masked lanes NaN-free; the post-softmax
    # where() then turns their uniform weights into zeros
    logits = jnp.where(valid, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(valid, w, 0.0)
    out = jnp.einsum("bkgt,bktd->bkgd", w, v)
    return out.astype(q.dtype)


def paged_prefill_ref(q, q_pos, k_pages, v_pages, pos_pages, page_table,
                      chunk_start, n_hist, ck, cv, c_pos, *, scale: float,
                      window: int | None = None):
    """Chunked-prefill attention over the paged pool (paged_prefill.py
    contract).

    q (B, Hkv, C, G, hd) chunk queries; q_pos (B, C) i32 (-1 = padded
    row, returns zeros); k/v_pages (P, Hkv, hd, ps) and pos_pages
    (P, 1, ps) i32 (-1 empty), as stored; page_table (B, maxp) i32;
    chunk_start (B,) i32 —
    pool history is clipped to kpos < start (the chunk's own positions
    come from the in-flight block, even if already scattered);
    n_hist (B,) i32 — table entries at index >= n_hist are ignored;
    ck/cv (B, Hkv, Cp, hd) in-flight chunk keys/values with positions
    c_pos (B, Cp) i32 (-1 padding), attended causally per query row.
    Returns (B, Hkv, C, G, hd).
    """
    b, hkv, c, g, hd = q.shape
    ps = k_pages.shape[-1]
    maxp = page_table.shape[1]
    kh = k_pages[page_table].astype(jnp.float32)    # (B, maxp, Hkv, hd, ps)
    vh = v_pages[page_table].astype(jnp.float32)
    kpos = pos_pages[page_table].reshape(b, maxp * ps)
    kh = kh.transpose(0, 2, 1, 4, 3).reshape(b, hkv, maxp * ps, hd)
    vh = vh.transpose(0, 2, 1, 4, 3).reshape(b, hkv, maxp * ps, hd)
    page_ok = jnp.repeat(jnp.arange(maxp)[None, :] < n_hist[:, None], ps,
                         axis=1)                    # (B, maxp*ps)
    hist_ok = (kpos >= 0) & (kpos < chunk_start[:, None]) & page_ok
    k_all = jnp.concatenate([kh, ck.astype(jnp.float32)], axis=2)
    v_all = jnp.concatenate([vh, cv.astype(jnp.float32)], axis=2)
    pos_all = jnp.concatenate([kpos, c_pos], axis=1)  # (B, T)
    ok_all = jnp.concatenate([hist_ok, c_pos >= 0], axis=1)
    valid = ok_all[:, None, :] & (pos_all[:, None, :]
                                  <= q_pos[:, :, None]) \
        & (q_pos[:, :, None] >= 0)                  # (B, C, T)
    if window is not None:
        valid &= pos_all[:, None, :] > (q_pos[:, :, None] - window)
    logits = jnp.einsum("bkcgd,bktd->bkcgt", q.astype(jnp.float32),
                        k_all) * scale
    valid = valid[:, None, :, None, :]              # (B, 1, C, 1, T)
    logits = jnp.where(valid, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(valid, w, 0.0)
    out = jnp.einsum("bkcgt,bktd->bkcgd", w, v_all)
    return out.astype(q.dtype)


def bellman_backup_ref(phi_next, trans, cost, mi_t):
    """T-Tamer Bellman backup (line_dp._backup contract).

    phi_next (K, X), trans (K, K), cost scalar, mi_t (K, X) int32 with
    mi_t[y, x] = X-axis index of min(xvals[x], grid[y]).
    Returns cont (K, X): cost + trans @ M, M[y,x] = phi_next[y, mi_t[y,x]].
    """
    m = jnp.take_along_axis(phi_next, mi_t, axis=1)
    return cost + trans @ m


def ssd_chunk_ref(xh, dt, da, bb, cc):
    """Within-chunk SSD (ssm.ssd_chunked inner term).

    xh (B,C,Q,H,P), dt/da (B,C,Q,H), bb/cc (B,C,Q,H,N).
    Returns (y_diag (B,C,Q,H,P), states (B,C,H,P,N)).
    """
    seg_a = da.swapaxes(-1, -2)                       # (B,C,H,Q)
    cs = jnp.cumsum(seg_a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    q = da.shape[2]
    mask = jnp.tril(jnp.ones((q, q), bool))
    l = jnp.where(mask, jnp.exp(diff), 0.0)
    scores = jnp.einsum("bcqhn,bckhn->bchqk", cc, bb)
    m = scores * l * dt.swapaxes(-1, -2)[..., None, :]
    y_diag = jnp.einsum("bchqk,bckhp->bcqhp", m, xh)
    cum = jnp.cumsum(da, axis=2)
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)
    w = decay_to_end * dt
    states = jnp.einsum("bcqh,bcqhn,bcqhp->bchpn", w, bb, xh)
    return y_diag, states


def ramp_exit_ref(logits, edges, stop_table, s_bin, x_idx, lam: float):
    """Fused T-Tamer exit decision (serving hot path).

    logits (B, V); edges (K-1,) support bucket edges; stop_table
    (K, K+2) int8 (1 = stop); s_bin/x_idx (B,) current policy state.

    Computes: conf = max softmax(logits); loss = lam * (1 - conf);
    bin = searchsorted(edges, loss); new_x = min(x_idx, bin + 1);
    stop = stop_table[bin, new_x].

    Returns (loss (B,), bin (B,) int32, new_x (B,) int32, stop (B,) bool).
    """
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    mx = logits.astype(jnp.float32).max(axis=-1)
    conf = jnp.exp(mx - lse)
    loss = lam * (1.0 - conf)
    b = jnp.searchsorted(edges, loss).astype(jnp.int32)
    new_x = jnp.minimum(x_idx, b + 1)
    stop = stop_table[b, new_x] > 0
    return loss, b, new_x, stop
