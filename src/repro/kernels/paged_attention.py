"""Paged-attention decode — Pallas TPU kernel (DESIGN.md §8).

One query token per lane attends over the lane's page-table slice of the
global KV pool (serving.kvpool).  The page INDIRECTION happens inside
the grid: grid (B*Hkv, lane_pages) with the page axis innermost, and the
k/v/pos BlockSpec index maps read ``table[lane, j]`` through scalar
prefetch — Mosaic streams exactly the pages the lane owns from HBM into
VMEM, so the (B, C) gathered cache the jnp path materializes never
exists.  Online softmax scratch (running max / sum / accumulator, per
q-head-group) lives in VMEM across page steps, exactly like
flash_attention.py's kv axis.

Masking is position-driven (matches the paged decode contract in
models/attention.py): a pool slot with stored position -1 is EMPTY
(garbage-sink writes, masked early-exit holes, reset pages) and
positions beyond the lane's query position (stale shared-page tails)
are masked by causality — plus the sliding window if configured.  Pages
past the lane's used count are skipped entirely with pl.when.

Block shapes: the (group x page_size) score tile wants page_size to be
a multiple of 128 on real TPUs (lane alignment; q group is padded to a
sublane multiple by ops.py).  Interpret mode (CPU CI) takes any shape.
The int32 position pages travel as (P, 1, ps) with (1, 1, ps) blocks:
Mosaic needs a block's last two dims divisible by (8, 128) or equal to
the array's, and a unit middle axis satisfies that at any page size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_kernel"]

NEG_INF = -1e30


def _kernel(table_ref, qpos_ref, nused_ref, q_ref, k_ref, v_ref, pos_ref,
            o_ref, m_scr, l_scr, acc_scr, *, scale: float,
            window: int | None, hkv: int):
    bh = pl.program_id(0)           # lane * Hkv + kv_head
    j = pl.program_id(1)            # index into the lane's page table
    nj = pl.num_programs(1)
    lane = bh // hkv

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j < nused_ref[lane])
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)         # (ps, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        kpos = pos_ref[0, 0]                        # (ps,) i32
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = qpos_ref[lane]
        valid = (kpos >= 0) & (kpos <= qp)
        if window is not None:
            valid &= kpos > qp - window
        s = jnp.where(valid[None, :], s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(valid[None, :], p, 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        # all-masked lanes (idle / nothing attendable) produce zeros
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window",
                                             "interpret"))
def paged_attention_kernel(q, k_pages, v_pages, pos_pages, page_table,
                           q_pos, n_used, *, scale: float,
                           window: int | None = None,
                           interpret: bool = False):
    """q (B, Hkv, G, hd); k/v_pages (P, Hkv, ps, hd); pos_pages (P, ps)
    i32; page_table (B, maxp) i32 (garbage-page padded); q_pos (B,) i32;
    n_used (B,) i32 pages to visit per lane.  hd % 128 == 0
    (ops.paged_attention pads).  Returns (B, Hkv, G, hd)."""
    b, hkv, g, hd = q.shape
    ps = k_pages.shape[2]
    maxp = page_table.shape[1]
    qf = q.reshape(b * hkv, g, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b * hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, g, hd),
                         lambda bh, j, t, qp, nu: (bh, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda bh, j, t, qp, nu, hkv=hkv:
                         (t[bh // hkv, j], bh % hkv, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda bh, j, t, qp, nu, hkv=hkv:
                         (t[bh // hkv, j], bh % hkv, 0, 0)),
            pl.BlockSpec((1, 1, ps),
                         lambda bh, j, t, qp, nu, hkv=hkv:
                         (t[bh // hkv, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, hd),
                               lambda bh, j, t, qp, nu: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, window=window,
                               hkv=hkv)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, hd), q.dtype),
        interpret=interpret,
    )(page_table, q_pos.astype(jnp.int32), n_used.astype(jnp.int32),
      qf, k_pages, v_pages, pos_pages[:, None, :])
    return out.reshape(b, hkv, g, hd)
