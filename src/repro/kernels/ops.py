"""jit'd public wrappers around the Pallas kernels: shape padding to tile
boundaries, dtype plumbing, and CPU dispatch (interpret=True executes the
kernel bodies in Python on CPU for correctness validation; on TPU the
same calls compile to Mosaic).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import (bellman_backup as _bb, flash_attention as _fa,
                           paged_attention as _pa,
                           paged_prefill as _pp, ramp_exit as _re,
                           ssd_chunk as _sc)

__all__ = ["flash_attention", "paged_attention", "paged_prefill",
           "bellman_backup", "ssd_chunk", "ramp_exit", "on_cpu"]


def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x, axis, mult, value=0.0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _stacked(k_pages, v_pages, pos_pages, layer):
    """The pool as the paged kernels read it: layer-stacked leaves (a
    single layer's pool gains a unit layer axis, a free reshape) and the
    layer to read as a (1,) i32 scalar prefetch."""
    if k_pages.ndim == 4:
        k_pages, v_pages, pos_pages = k_pages[None], v_pages[None], \
            pos_pages[None]
    return (k_pages, v_pages, pos_pages,
            jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)))


def flash_attention(q, k, v, *, scale: float, causal: bool = True,
                    window: int | None = None, block_q: int = 128,
                    block_kv: int = 128, interpret: bool | None = None):
    """q (B,S,H,hd), k/v (B,S,Hkv,hd) — model layout; returns same.

    Pads hd to 128 and S to the block size (padded kv is masked out by
    the causal mask since padded queries/keys sit at the tail)."""
    interpret = on_cpu() if interpret is None else interpret
    b, s, h, hd = q.shape
    qt = _pad_to(q.transpose(0, 2, 1, 3), 3, 128)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 3, 128)
    vt = _pad_to(v.transpose(0, 2, 1, 3), 3, 128)
    s_pad = max(block_q, block_kv)
    qt = _pad_to(qt, 2, s_pad)
    kt = _pad_to(kt, 2, s_pad)
    vt = _pad_to(vt, 2, s_pad)
    out = _fa.flash_attention_kernel(qt, kt, vt, scale=scale, causal=causal,
                                     window=window, block_q=block_q,
                                     block_kv=block_kv, interpret=interpret)
    return out[:, :, :s, :hd].transpose(0, 2, 1, 3)


def paged_attention(q, k_pages, v_pages, pos_pages, page_table, q_pos, *,
                    scale: float, window: int | None = None, layer=0,
                    interpret: bool | None = None):
    """Paged single-token decode attention — model layout in/out.

    q (B, H, hd) with H = G * Hkv; k/v_pages (P, Hkv, hd, page) and
    pos_pages (P, 1, page) i32 (-1 empty) — the pool as stored
    (DESIGN.md §8), or one segment's layer-stacked pool with a leading
    L axis and ``layer`` (an i32 scalar, traced or not) picking the
    layer, read in place; page_table (B, maxp) i32 garbage-page padded;
    q_pos (B,) i32.  Pads the q group to a sublane multiple of 8 and
    derives the per-lane visited-page count from q_pos; hd is not
    padded.  Returns (B, H, hd).
    """
    interpret = on_cpu() if interpret is None else interpret
    b, h, hd = q.shape
    ps = k_pages.shape[-1]
    hkv = k_pages.shape[-3]
    g = h // hkv
    gp = -(-g // 8) * 8
    qg = _pad_to(q.reshape(b, hkv, g, hd), 2, gp)
    k_pages, v_pages, pos_pages, layer = _stacked(k_pages, v_pages,
                                                  pos_pages, layer)
    q_pos = q_pos.astype(jnp.int32)
    n_used = jnp.minimum(q_pos // ps + 1, page_table.shape[1])
    out = _pa.paged_attention_kernel(
        qg, k_pages, v_pages, pos_pages.astype(jnp.int32),
        page_table.astype(jnp.int32), q_pos, n_used, layer, scale=scale,
        window=window, interpret=interpret)
    return out[:, :, :g].reshape(b, h, hd)


def paged_prefill(q, k_pages, v_pages, pos_pages, page_table, q_pos,
                  chunk_start, ck, cv, c_pos, *, scale: float,
                  window: int | None = None, layer=0,
                  interpret: bool | None = None):
    """Chunked-prefill attention over the paged pool — model layout.

    q (B, C, H, hd) chunk queries with H = G * Hkv and per-row positions
    q_pos (B, C) i32 (-1 = padded row); k/v_pages (P, Hkv, hd, page) and
    pos_pages (P, 1, page) i32 — the pool as stored, or layer-stacked
    with ``layer`` picking the layer (as `paged_attention`);
    page_table (B, maxp) i32; chunk_start (B,) i32 (history clipped to
    kpos < start); ck/cv (B, C, Hkv, hd) the chunk's own in-flight
    keys/values at positions c_pos (B, C).  Pads the q group to a
    sublane multiple of 8 and the chunk-key axis to 128, and derives the
    history page count from chunk_start; hd is not padded.  Returns
    (B, C, H, hd).
    """
    interpret = on_cpu() if interpret is None else interpret
    b, c, h, hd = q.shape
    ps = k_pages.shape[-1]
    hkv = k_pages.shape[-3]
    g = h // hkv
    gp = -(-g // 8) * 8
    # (B, C, H, hd) -> (B, Hkv, C, G, hd): row c*G + g is query (c, g)
    qg = q.reshape(b, c, hkv, g, hd).transpose(0, 2, 1, 3, 4)
    qg = _pad_to(qg, 3, gp).reshape(b, hkv, c * gp, hd)
    k_pages, v_pages, pos_pages, layer = _stacked(k_pages, v_pages,
                                                  pos_pages, layer)
    cp = -(-c // 128) * 128
    ckt = _pad_to(ck.transpose(0, 2, 1, 3), 2, cp)
    cvt = _pad_to(cv.transpose(0, 2, 1, 3), 2, cp)
    c_pos_p = _pad_to(c_pos.astype(jnp.int32), 1, cp, value=-1)
    chunk_start = chunk_start.astype(jnp.int32)
    n_hist = jnp.clip(-(-chunk_start // ps), 0, page_table.shape[1])
    out = _pp.paged_prefill_kernel(
        qg, q_pos.astype(jnp.int32), k_pages, v_pages,
        pos_pages.astype(jnp.int32), page_table.astype(jnp.int32),
        chunk_start, n_hist, ckt, cvt, c_pos_p, layer, scale=scale,
        window=window, interpret=interpret)
    out = out.reshape(b, hkv, c, gp, hd)[:, :, :, :g]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, c, h, hd)


def bellman_backup(phi_next, trans, cost, mi_t, *,
                   interpret: bool | None = None):
    """Drop-in for line_dp._backup's fused path: returns cont (K, X)."""
    interpret = on_cpu() if interpret is None else interpret
    k, x = phi_next.shape
    # pad X to 128 with repeats of the last column (harmless: extra states)
    xp = (-x) % 128
    if xp:
        phi_next = jnp.pad(phi_next, ((0, 0), (0, xp)), mode="edge")
        mi_t = jnp.pad(mi_t, ((0, 0), (0, xp)), mode="edge")
    cont = _bb.bellman_backup_kernel(phi_next, trans, cost, mi_t,
                                     interpret=interpret)
    return cont[:, :x]


def ssd_chunk(xh, dt, da, bb, cc, *, interpret: bool | None = None):
    """Within-chunk SSD; see ssd_chunk.py.  Shapes pass through."""
    interpret = on_cpu() if interpret is None else interpret
    y, s = _sc.ssd_chunk_kernel(xh, dt, da, bb, cc, interpret=interpret)
    return y.astype(xh.dtype), s.astype(xh.dtype)


def ramp_exit(logits, edges, stop_table, s_bin, x_idx, *, lam: float,
              interpret: bool | None = None):
    """Fused exit decision; logits (B, V).  Returns (loss, bin, new_x,
    stop) per lane."""
    interpret = on_cpu() if interpret is None else interpret
    b, v = logits.shape
    logits_p = _pad_to(logits, 1, 2048, value=-1e30)
    bb_pad = (-b) % 8
    if bb_pad:
        logits_p = jnp.pad(logits_p, ((0, bb_pad), (0, 0)),
                           constant_values=-1e30)
        s_bin = jnp.pad(s_bin, (0, bb_pad))
        x_idx = jnp.pad(x_idx, (0, bb_pad))
    loss, bins, newx, stop = _re.ramp_exit_kernel(
        logits_p, edges, stop_table, s_bin, x_idx, lam=lam,
        interpret=interpret)
    return loss[:b], bins[:b], newx[:b], stop[:b]
