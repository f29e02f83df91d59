"""Chunked-prefill flash attention over the paged KV pool — Pallas TPU
kernel (DESIGN.md §9).

One PREFILL CHUNK of C query tokens per lane attends over (a) the
lane's page-table history — everything earlier chunks already committed
to the pool — and (b) the chunk's own in-flight keys, causally.  This
is the device side of Sarathi-style chunked prefill: the chunk runs
inside the same program as decode, and its history reads go through the
SAME page indirection as the paged decode kernel
(kernels/paged_attention.py) — grid ``(B*Hkv, maxp + 1)`` with the page
axis innermost, k/v/pos BlockSpec index maps reading ``table[lane, j]``
via scalar prefetch, online-softmax scratch in VMEM across the kv axis.
The final grid step (``j == maxp``) switches to the chunk's in-flight
k/v block (resident in VMEM for every j — it is small), so the kernel
never materializes the (B, C_hist + C) gathered tensor the jnp path
builds.

Masking contract (matches models/attention.py `attn_prefill_chunk`):

  * pool slots with stored position -1 are EMPTY (garbage-sink writes,
    masked early-exit holes, freshly reset pages) — never attended;
  * pool history is clipped to ``kpos < chunk_start[lane]`` — the
    chunk's OWN positions may already have been scattered into the pool
    before the kernel runs (commit order is scatter-then-attend), and
    they must come from the in-flight block instead, exactly once;
  * the in-flight block is causal per query row (``ckpos <= qpos``);
    query rows padded with position -1 (ragged final chunks, idle
    prefill slots) have nothing attendable and return zeros;
  * a sliding window drops keys at ``kpos <= qpos - window`` on both
    sides.

Pages past ``ceil(chunk_start / ps)`` are skipped with pl.when.  The
pool is read in place, in its stored layout (DESIGN.md §8): one
segment's layer-stacked ``k/v (L, P, Hkv, hd, ps)`` and ``pos (L, P, 1,
ps)``, the layer picked by a scalar prefetch; a history page of one
head is a (hd, ps) tile, dense at hd 64, so history scores are ``q @
k_page`` and its output ``p @ v_page^T``.  Block shapes: the (C*G, ps)
score tile wants ps and the chunk-key axis padded to 128 on real TPUs
and C*G to a sublane multiple (ops.py pads); interpret mode (CPU CI)
takes any shape.  The int32 position arrays (pool pages, query rows,
chunk keys) travel with a unit axis, (1, W) blocks, so their last two
block dims satisfy Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_prefill_kernel"]

NEG_INF = -1e30


def _kernel(table_ref, start_ref, nhist_ref, layer_ref, q_ref, qpos_ref,
            k_ref, v_ref, pos_ref, ck_ref, cv_ref, cpos_ref, o_ref, m_scr,
            l_scr, acc_scr, *, scale: float, window: int | None, hkv: int,
            g: int):
    bh = pl.program_id(0)           # lane * Hkv + kv_head
    j = pl.program_id(1)            # page index; j == nj-1 = in-chunk block
    nj = pl.num_programs(1)
    lane = bh // hkv

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _accumulate(k, v, valid, kdim):
        """One online-softmax block update.  k/v f32, (T, hd) with
        ``kdim`` 1 (keys as rows) or (hd, T) with ``kdim`` 0 (a pool
        page); valid (C, G?, T) broadcastable to the (C, G, T) score
        tile."""
        q = q_ref[0].astype(jnp.float32)                  # (C*G, hd)
        s = jax.lax.dot_general(q, k, (((1,), (kdim,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        t = s.shape[1]
        c = q.shape[0] // g
        s = s.reshape(c, g, t)
        s = jnp.where(valid, s, NEG_INF).reshape(c * g, t)
        pv = jnp.broadcast_to(valid, (c, g, t)).reshape(c * g, t)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(pv, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (1 - kdim,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when((j < nj - 1) & (j < nhist_ref[lane]))
    def _history_page():
        kpos = pos_ref[0]                                 # (ps,) i32
        qp = qpos_ref[0, 0]                               # (C,) i32
        valid = (kpos[None, :] >= 0) \
            & (kpos[None, :] < start_ref[lane]) \
            & (kpos[None, :] <= qp[:, None])
        if window is not None:
            valid &= kpos[None, :] > qp[:, None] - window
        _accumulate(k_ref[...].astype(jnp.float32),      # (hd, ps)
                    v_ref[...].astype(jnp.float32),
                    valid[:, None, :], 0)

    @pl.when(j == nj - 1)
    def _in_chunk():
        ckpos = cpos_ref[0, 0]                            # (Cp,) i32
        qp = qpos_ref[0, 0]                               # (C,) i32
        valid = (ckpos[None, :] >= 0) & (qp[:, None] >= 0) \
            & (ckpos[None, :] <= qp[:, None])
        if window is not None:
            valid &= ckpos[None, :] > qp[:, None] - window
        _accumulate(ck_ref[0].astype(jnp.float32),       # (Cp, hd)
                    cv_ref[0].astype(jnp.float32),
                    valid[:, None, :], 1)
        # all-masked rows (position -1 padding) produce zeros
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window",
                                             "interpret"))
def paged_prefill_kernel(q, q_pos, k_pages, v_pages, pos_pages, page_table,
                         chunk_start, n_hist, ck, cv, c_pos, layer, *,
                         scale: float, window: int | None = None,
                         interpret: bool = False):
    """q (B, Hkv, C*G, hd) chunk queries (rows grouped by position: row
    ``c*G + g``); q_pos (B, C) i32 per-row positions (-1 = padded row);
    k/v_pages (L, P, Hkv, hd, ps) pool; pos_pages (L, P, 1, ps) i32;
    page_table (B, maxp) i32 garbage-padded; chunk_start (B,) i32
    (history reads are clipped to kpos < start); n_hist (B,) i32
    history pages to visit; ck/cv (B, Hkv, Cp, hd) in-flight chunk
    keys/values; c_pos (B, Cp) i32 their positions (-1 = padding);
    layer (1,) i32, the layer of the stacked pool to read.  Returns
    (B, Hkv, C*G, hd)."""
    b, hkv, cg, hd = q.shape
    c = q_pos.shape[1]
    g = cg // c
    ps = k_pages.shape[-1]
    maxp = page_table.shape[1]
    cp = ck.shape[2]
    qf = q.reshape(b * hkv, cg, hd)
    ckf = ck.reshape(b * hkv, cp, hd)
    cvf = cv.reshape(b * hkv, cp, hd)

    def page(bh, j, t, st, nh, ly):
        return (ly[0], t[bh // hkv, jnp.minimum(j, maxp - 1)], bh % hkv,
                0, 0)

    def lane(bh, j, t, st, nh, ly):
        return (bh // hkv, 0, 0)

    def row(bh, j, t, st, nh, ly):
        return (bh, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b * hkv, maxp + 1),
        in_specs=[
            pl.BlockSpec((1, cg, hd), row),
            pl.BlockSpec((1, 1, c), lane),
            pl.BlockSpec((None, None, None, hd, ps), page),
            pl.BlockSpec((None, None, None, hd, ps), page),
            pl.BlockSpec((None, None, 1, ps),
                         lambda bh, j, t, st, nh, ly:
                         (ly[0], t[bh // hkv, jnp.minimum(j, maxp - 1)],
                          0, 0)),
            pl.BlockSpec((1, cp, hd), row),
            pl.BlockSpec((1, cp, hd), row),
            pl.BlockSpec((1, 1, cp), lane),
        ],
        out_specs=pl.BlockSpec((1, cg, hd), row),
        scratch_shapes=[
            pltpu.VMEM((cg,), jnp.float32),
            pltpu.VMEM((cg,), jnp.float32),
            pltpu.VMEM((cg, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, window=window,
                               hkv=hkv, g=g)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, cg, hd), q.dtype),
        interpret=interpret,
    )(page_table, chunk_start.astype(jnp.int32), n_hist.astype(jnp.int32),
      layer.astype(jnp.int32), qf, q_pos.astype(jnp.int32)[:, None, :],
      k_pages, v_pages, pos_pages, ckf, cvf,
      c_pos.astype(jnp.int32)[:, None, :])
    return out.reshape(b, hkv, cg, hd)
