"""Attention mixers: GQA (with qk-norm / sliding window) and MLA
(DeepSeek-V2 Multi-head Latent Attention), each with a training/prefill
path and a single-token decode path against EITHER a per-lane
ring-buffer KV cache or the paged KV pool (DESIGN.md §3, §8).

Ring cache layout (fixed shapes — TPU-friendly):
  GQA:  k, v: (B, C, Hkv, hd); pos: (B, C) absolute positions (-1 empty).
  MLA:  c_kv: (B, C, lora); k_rope: (B, C, rope_dim); pos: (B, C).
C = min(seq_len, window) — sliding windows bound the decode cache.

Paged layout (serving.kvpool, DESIGN.md §8): the SAME leaf names with
the lane axis replaced by a global page pool and the page-slot axis
minor — ``k, v: (P, Hkv, hd, page)``, ``pos: (P, 1, page)`` (each ring
leaf ``(B, C, *row)`` becomes ``(P, *row or (1,), page)``,
`pool_cache_defs`) — plus a per-lane `PagedKV` handle carrying the page
table and this token's (page, slot) write target.  This is the layout
the paged kernels read, so the pool is never relaid: at hd 64 and a
page of 128 a head's page is a dense (64, 128) tile, where an hd-minor
pool would pad hd to 128 lanes.  Writes are in place: `pool_write`
reads the few pages a step's rows land in, sets those slots and writes
the pages back (a whole-page window, which XLA scatters in this layout
without relaying the pool).  In serving, the pool is one segment's
layer-stacked leaves ``(L, P, ...)``, carried whole through the layer
loop, and each layer reads and writes its own layer by index.  Page 0
is the reserved garbage sink: unused page-table entries point at it
and it never holds a position (-1 throughout), so gathered garbage is
never attended; lanes masked out by ``write_mask`` (early-exited or
unoccupied) write nothing, and the holes they leave behind are hidden
by the SAME stored-position mask the ring path uses.

The einsum/jnp path is what the multi-pod dry-run lowers (XLA fuses it and
GSPMD shards it); the Pallas flash kernel (repro.kernels.flash_attention)
is the TPU hot-path for prefill, the paged-attention kernel
(repro.kernels.paged_attention, enabled via ``paged_kernel(True)``) the
hot-path for paged decode — both validated against `kernels.ref` in
interpret mode.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.common import causal_mask, rms_norm, rope, rope_cos_sin
from repro.models.config import AttnConfig
from repro.models.param import ParamDef

__all__ = ["attn_defs", "attn_forward", "attn_decode",
           "attn_prefill_chunk", "init_cache_defs", "pool_cache_defs",
           "pool_targets", "pool_write", "PagedKV", "PrefillChunk",
           "paged_kernel"]

# must agree with serving.kvpool.alloc.GARBAGE_PAGE (kept as a literal so
# the model layer never imports the serving layer)
_GARBAGE_PAGE = 0


class PagedKV(NamedTuple):
    """Per-token device view of a lane's paged-KV state (a pytree; the
    host-side planner is serving.kvpool.KVPool)."""

    page_table: jax.Array   # (B, lane_pages) i32, garbage-page padded
    write_page: jax.Array   # (B,) i32 page receiving this token's KV
    write_slot: jax.Array   # (B,) i32 slot within that page


class PrefillChunk(NamedTuple):
    """Per-step device view of the prefill chunks co-scheduled with
    decode (DESIGN.md §9): up to C prompt tokens per admitting lane,
    planned host-side by the scheduler's chunk planner.  All arrays are
    (B, C) / (B,) with idle lanes and ragged tails padded: position -1
    rows are inert, garbage-page destinations swallow their writes."""

    tok: jax.Array          # (B, C) i32 chunk tokens (0 for padding)
    pos: jax.Array          # (B, C) i32 absolute positions (-1 = pad)
    dest_page: jax.Array    # (B, C) i32 pool page per token (garbage =
                            #   prefix-cache hit / padding: no write)
    dest_slot: jax.Array    # (B, C) i32 slot within the page
    start: jax.Array        # (B,) i32 chunk-start position (pool
                            #   history is read strictly below this)
    last_idx: jax.Array     # (B,) i32 row of the chunk's final valid
                            #   token (the readout position when emit)
    emit: jax.Array         # (B,) bool final chunk: emit first token
    active: jax.Array       # (B,) bool lanes prefilling this step


# --------------------------------------------------------------------------
# Parameter definitions
# --------------------------------------------------------------------------

def attn_defs(cfg: AttnConfig, d_model: int) -> dict:
    if cfg.mla is not None:
        m = cfg.mla
        h = cfg.n_heads
        defs = {
            "wq": ParamDef((d_model, h * (m.qk_nope_head_dim
                                          + m.qk_rope_head_dim)),
                           ("embed", "heads")),
            "w_dkv": ParamDef((d_model, m.kv_lora_rank + m.qk_rope_head_dim),
                              ("embed", None)),
            "kv_norm": ParamDef((m.kv_lora_rank,), (None,), init="ones"),
            "w_uk": ParamDef((m.kv_lora_rank, h * m.qk_nope_head_dim),
                             (None, "heads")),
            "w_uv": ParamDef((m.kv_lora_rank, h * m.v_head_dim),
                             (None, "heads")),
            "wo": ParamDef((h * m.v_head_dim, d_model), ("heads", "embed")),
        }
        if m.q_lora_rank:
            defs["w_dq"] = ParamDef((d_model, m.q_lora_rank), ("embed", None))
            defs["q_norm"] = ParamDef((m.q_lora_rank,), (None,), init="ones")
            defs["wq"] = ParamDef(
                (m.q_lora_rank, h * (m.qk_nope_head_dim + m.qk_rope_head_dim)),
                (None, "heads"))
        return defs

    defs = {
        "wq": ParamDef((d_model, cfg.n_heads * cfg.head_dim),
                       ("embed", "heads")),
        "wk": ParamDef((d_model, cfg.n_kv_heads * cfg.head_dim),
                       ("embed", "kv_heads")),
        "wv": ParamDef((d_model, cfg.n_kv_heads * cfg.head_dim),
                       ("embed", "kv_heads")),
        "wo": ParamDef((cfg.n_heads * cfg.head_dim, d_model),
                       ("heads", "embed")),
    }
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((cfg.head_dim,), (None,), init="ones")
        defs["k_norm"] = ParamDef((cfg.head_dim,), (None,), init="ones")
    return defs


def init_cache_defs(cfg: AttnConfig, batch: int, cache_len: int) -> dict:
    """ShapeDtypeStruct-compatible cache spec (used by input_specs).

    Under the `cache_int8` context the K/V (or MLA latent) tensors are
    int8 with per-(position, head) bf16 scales — models.quant."""
    from repro.models.quant import int8_enabled
    i8 = int8_enabled()
    kv_dt = jnp.int8 if i8 else jnp.bfloat16
    if cfg.mla is not None:
        m = cfg.mla
        out = {
            "c_kv": ((batch, cache_len, m.kv_lora_rank), kv_dt),
            "k_rope": ((batch, cache_len, m.qk_rope_head_dim), kv_dt),
            "pos": ((batch, cache_len), jnp.int32),
        }
        if i8:
            out["c_kv_s"] = ((batch, cache_len), jnp.bfloat16)
            out["k_rope_s"] = ((batch, cache_len), jnp.bfloat16)
        return out
    out = {
        "k": ((batch, cache_len, cfg.n_kv_heads, cfg.head_dim), kv_dt),
        "v": ((batch, cache_len, cfg.n_kv_heads, cfg.head_dim), kv_dt),
        "pos": ((batch, cache_len), jnp.int32),
    }
    if i8:
        out["k_s"] = ((batch, cache_len, cfg.n_kv_heads), jnp.bfloat16)
        out["v_s"] = ((batch, cache_len, cfg.n_kv_heads), jnp.bfloat16)
    return out


def pool_cache_defs(cfg: AttnConfig, n_pages: int, page_size: int) -> dict:
    """One layer's paged-pool spec (DESIGN.md §8): each leaf of the ring
    cache ``(B, C, *row)`` becomes ``(P, *row, page)``, with a unit row
    for the per-position leaves (``pos``, MLA's int8 scales) — the
    page-slot axis minor, the layout the paged kernels read."""
    return {name: ((n_pages,) + (shape[2:] or (1,)) + (page_size,), dt)
            for name, (shape, dt) in init_cache_defs(
                cfg, n_pages, page_size).items()}


def pool_targets(page, off, live, n_win: int, ps: int):
    """The pages of one pool write.  A lane's rows (B, C) hold
    consecutive positions: row i lands in slot ``off + i`` of the run of
    ``n_win`` pages its rows span (``off`` (B,) the first row's slot),
    in page ``page[:, i]``; ``live`` marks the rows that land at all.
    Returns (pages (B, n_win) i32, the pages of the run, garbage where
    no row lands; off; live) — the targets `pool_write` takes."""
    b, c = page.shape
    win = jnp.where(live, (off[:, None] + jnp.arange(c)[None]) // ps,
                    n_win)                     # dead rows: a dropped column
    pages = jnp.full((b, n_win + 1), _GARBAGE_PAGE, jnp.int32).at[
        jnp.arange(b)[:, None], win].max(
            jnp.where(live, page, _GARBAGE_PAGE))
    return pages[:, :n_win], off, live


# the scope names the pool's writes in a device trace; it must not name
# a kernel, or the trace reduction would count it as one
@jax.named_scope("page_write")
def pool_write(leaf, layer, targets, rows):
    """Write rows (B, C, *row) into ``layer`` of a layer-stacked pool
    leaf (L, P, *row, ps) in place: read the pages `pool_targets` names,
    set the live rows' slots, write the pages back.  Only whole pages
    move (windows XLA scatters without relaying the pool), and only the
    few a step writes.  A single row is broadcast over its page and
    selected by slot; consecutive rows are shifted to their slots as a
    block, one slice per lane (no per-row gather)."""
    pages, off, live = targets
    b, n_win = pages.shape
    c, ps = rows.shape[1], leaf.shape[-1]
    old = leaf[layer, pages]                              # (B, J, *row, ps)
    r = rows.reshape(b, c, -1).astype(leaf.dtype)
    if c == 1:
        hit = (jnp.arange(ps)[None] == off[:, None]) & live
        new = r.reshape(old.shape[:-1] + (1,))
    else:
        n = n_win * ps

        def shift(x, o):                 # x[ps + i] -> slot o + i of run
            return jax.lax.dynamic_slice_in_dim(x, ps - o, n)

        pad = ((0, 0), (ps, n - c))
        new = jax.vmap(shift)(jnp.pad(r, pad + ((0, 0),)), off)
        hit = jax.vmap(shift)(jnp.pad(live, pad), off)
        new = jnp.moveaxis(new.reshape(b, n_win, ps, -1), 2, -1).reshape(
            old.shape)
    hit = hit.reshape((b, n_win) + (1,) * (old.ndim - 3) + (ps,))
    return leaf.at[layer, pages].set(jnp.where(hit, new, old))


def _pool_commit(pool: dict, layer, targets, rows: dict) -> dict:
    """`pool_write` of every leaf that has rows."""
    out = dict(pool)
    for name, r in rows.items():
        out[name] = pool_write(pool[name], layer, targets, r)
    return out


def _pool_gather(leaf, layer, table):
    """The lanes' pages of ``layer`` as rows: (B, maxp * ps, *row)."""
    g = leaf[layer, table]                         # (B, maxp, *row, ps)
    g = jnp.moveaxis(g, -1, 2)
    return g.reshape((g.shape[0], -1) + g.shape[3:])


def _decode_targets(paged: "PagedKV", write_mask, ps: int):
    """Targets of one decode token per lane: the lane's (page, slot),
    or nothing for lanes masked out by ``write_mask``."""
    wp = paged.write_page[:, None]
    live = jnp.ones_like(wp, bool) if write_mask is None \
        else write_mask[:, None]
    return pool_targets(wp, paged.write_slot, live, 1, ps)


# --------------------------------------------------------------------------
# GQA
# --------------------------------------------------------------------------

def _split_heads(x, n_heads, head_dim):
    return x.reshape(*x.shape[:-1], n_heads, head_dim)


_CHUNK_THRESHOLD = 16_384  # chunk queries above this seq len
_Q_CHUNK = 2_048

# Prefill attention implementation (perf hillclimb lever, EXPERIMENTS.md
# §Perf): "chunked" = lax.map over query chunks with FULL kv columns
# (paper-faithful baseline); "banded" = per-chunk kv slicing — causal
# chunks only read kv[0 : chunk_end], windowed chunks only the
# [chunk_start - window, chunk_end) band, cutting score traffic ~2x
# (causal) to ~S/(Qc+w) (windowed).
import contextlib
import contextvars

_ATTN_IMPL = contextvars.ContextVar("repro_attn_impl", default="banded")


@contextlib.contextmanager
def attention_impl(name: str):
    assert name in ("chunked", "banded")
    tok = _ATTN_IMPL.set(name)
    try:
        yield
    finally:
        _ATTN_IMPL.reset(tok)


def _sdpa_chunked(q, k, v, q_pos, kv_pos, window, scale):
    """Query-chunked attention: never materializes the (S, S) score matrix
    — the XLA-level analogue of flash attention, used for long prefill
    (the Pallas kernel is the TPU hot path; this keeps the dry-run's
    memory_analysis honest).  q (B,S,H,hd); k,v (B,T,Hkv,*)."""
    if _ATTN_IMPL.get() == "banded":
        return _sdpa_banded(q, k, v, q_pos, kv_pos, window, scale)
    b, s, h, hd = q.shape
    nc = s // _Q_CHUNK
    assert s % _Q_CHUNK == 0, "caller pads to the chunk size"
    qc = q.reshape(b, nc, _Q_CHUNK, h, hd).swapaxes(0, 1)
    pc = q_pos.reshape(b, nc, _Q_CHUNK).swapaxes(0, 1)

    def one(args):
        q_i, p_i = args
        mask = causal_mask(p_i, kv_pos, window)
        return _sdpa(q_i, k, v, mask, scale)

    out = jax.lax.map(one, (qc, pc))
    return out.swapaxes(0, 1).reshape(b, s, h, -1)


_CAUSAL_GROUPS = 4  # causal banding: unroll factor (bounds live buffers)


def _sdpa_banded(q, k, v, q_pos, kv_pos, window, scale):
    """Banded chunked attention (EXPERIMENTS.md §Perf): each query chunk
    reads only the kv it can attend to, with bounded live memory.

    * windowed: constant-size band (window rounded up to a chunk + one
      chunk), gathered with lax.dynamic_slice inside lax.map — buffers are
      reused across chunks, traffic/FLOPs drop ~S/(w+Qc).
    * causal: chunks are processed in _CAUSAL_GROUPS groups; group g's
      chunks run under one lax.map against kv[: group_end] — ~1.6x
      traffic/FLOPs cut at unroll factor 4 (limit 2x), no 16x live set.

    Assumes the standard prefill layout (q_pos == kv_pos, contiguous)."""
    b, s, h, hd = q.shape
    qc = _Q_CHUNK
    nc = s // qc

    if window is not None:
        band = ((window + qc - 1) // qc + 1) * qc      # static band size
        band = min(band, s)
        kp = jnp.pad(k, ((0, 0), (band - qc, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (band - qc, 0), (0, 0), (0, 0)))
        # padded kv position j corresponds to absolute j - (band - qc)
        pad_pos = jnp.pad(kv_pos, ((0, 0), (band - qc, 0)),
                          constant_values=-1)
        qg = q.reshape(b, nc, qc, h, hd).swapaxes(0, 1)
        pg = q_pos.reshape(b, nc, qc).swapaxes(0, 1)
        idx = jnp.arange(nc)

        def one(args):
            q_i, p_i, i = args
            start = i * qc  # band ends at chunk end in padded coords
            k_i = jax.lax.dynamic_slice_in_dim(kp, start, band, axis=1)
            v_i = jax.lax.dynamic_slice_in_dim(vp, start, band, axis=1)
            kp_i = jax.lax.dynamic_slice_in_dim(pad_pos, start, band,
                                                axis=1)
            mask = causal_mask(p_i, kp_i, window) & (kp_i >= 0)[:, None, :]
            return _sdpa(q_i, k_i, v_i, mask, scale)

        out = jax.lax.map(one, (qg, pg, idx))
        return out.swapaxes(0, 1).reshape(b, s, h, -1)

    # causal: grouped prefix banding
    groups = min(_CAUSAL_GROUPS, nc)
    assert nc % groups == 0
    per = nc // groups
    outs = []
    for g in range(groups):
        lo, hi = g * per * qc, (g + 1) * per * qc
        qg = q[:, lo:hi].reshape(b, per, qc, h, hd).swapaxes(0, 1)
        pg = q_pos[:, lo:hi].reshape(b, per, qc).swapaxes(0, 1)
        k_g, v_g = k[:, :hi], v[:, :hi]
        kp_g = kv_pos[:, :hi]

        def one(args, k_g=k_g, v_g=v_g, kp_g=kp_g):
            q_i, p_i = args
            mask = causal_mask(p_i, kp_g, None)
            return _sdpa(q_i, k_g, v_g, mask, scale)

        og = jax.lax.map(one, (qg, pg))
        outs.append(og.swapaxes(0, 1).reshape(b, hi - lo, h, -1))
    return jnp.concatenate(outs, axis=1)


def _sdpa(q, k, v, mask, scale):
    """q (B,S,H,hd), k (B,T,Hkv,hd), v (B,T,Hkv,vd) with H = G*Hkv
    (vd may differ from hd, e.g. MLA).  mask (B,S,T) or (S,T)."""
    b, s, h, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    vd = v.shape[-1]
    g = h // hkv
    q = q.reshape(b, s, hkv, g, hd)
    logits = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None]
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(b, s, h, vd)


def attn_forward(p: dict, x: jax.Array, positions: jax.Array,
                 cfg: AttnConfig, eps: float = 1e-5,
                 use_flash: bool = False):
    """Full self-attention (train / prefill).

    Returns (y, cache_entries) where cache_entries holds what decode needs.
    """
    if cfg.mla is not None:
        return _mla_forward(p, x, positions, cfg, eps)
    b, s, d = x.shape
    q = _split_heads(x @ p["wq"], cfg.n_heads, cfg.head_dim)
    k = _split_heads(x @ p["wk"], cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(x @ p["wv"], cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm({"scale": p["q_norm"]}, q, eps)
        k = rms_norm({"scale": p["k_norm"]}, k, eps)
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                            cfg.yarn)
    q = rope(q, cos, sin)
    k = rope(k, cos, sin)
    scale = cfg.softmax_scale or 1.0 / math.sqrt(cfg.head_dim)
    if use_flash:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, scale=scale, causal=True,
                                   window=cfg.window)
    elif s >= _CHUNK_THRESHOLD and s % _Q_CHUNK == 0:
        out = _sdpa_chunked(q, k, v, positions, positions, cfg.window, scale)
    else:
        mask = causal_mask(positions, positions, cfg.window)
        out = _sdpa(q, k, v, mask, scale)
    y = out.reshape(b, s, -1) @ p["wo"]
    return y, {"k": k, "v": v}


# Paged-decode attention implementation (DESIGN.md §8): "gather" (the
# default — page-table gather + the same _sdpa as the ring path, what
# XLA lowers everywhere) or the Pallas paged-attention kernel
# (repro.kernels.paged_attention — page indirection inside the grid via
# scalar prefetch; GQA bf16/f32 only, int8 and MLA fall back to gather).
_PAGED_KERNEL = contextvars.ContextVar("repro_paged_kernel", default=False)


@contextlib.contextmanager
def paged_kernel(on: bool = True):
    tok = _PAGED_KERNEL.set(on)
    try:
        yield
    finally:
        _PAGED_KERNEL.reset(tok)


def _gqa_qkv_decode(p: dict, x: jax.Array, pos: jax.Array, cfg: AttnConfig,
                    eps: float):
    """The new token's q/k/v (+ qk-norm + rope), shared by the ring and
    paged decode paths.  x (B,1,D) -> q/k/v (B,1,H*,hd)."""
    q = _split_heads(x @ p["wq"], cfg.n_heads, cfg.head_dim)
    k = _split_heads(x @ p["wk"], cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(x @ p["wv"], cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm({"scale": p["q_norm"]}, q, eps)
        k = rms_norm({"scale": p["k_norm"]}, k, eps)
    cos, sin = rope_cos_sin(pos[:, None], cfg.head_dim, cfg.rope_theta,
                            cfg.yarn)
    return rope(q, cos, sin), rope(k, cos, sin), v


def _kv_rows(pool: dict, k, v, pos) -> dict:
    """The rows a write stores: k/v (B, C, Hkv, hd) in the pool's dtype
    (int8 with per-(position, head) scales under models.quant) and the
    positions (B, C)."""
    if "k_s" in pool:
        from repro.models.quant import quantize_rows
        kq, ks = quantize_rows(k)
        vq, vs = quantize_rows(v)
        rows = {"k": kq, "v": vq, "k_s": ks, "v_s": vs}
    else:
        rows = {"k": k.astype(pool["k"].dtype),
                "v": v.astype(pool["v"].dtype)}
    rows["pos"] = pos.astype(jnp.int32)
    return rows


def _kv_history(pool: dict, layer, table, dtype):
    """The jnp gather path's view of the lanes' pages: k/v (B, T, Hkv,
    hd) in ``dtype`` and positions (B, T), T = maxp * ps."""
    k = _pool_gather(pool["k"], layer, table)
    v = _pool_gather(pool["v"], layer, table)
    if "k_s" in pool:
        from repro.models.quant import dequantize_rows
        k = dequantize_rows(k, _pool_gather(pool["k_s"], layer, table),
                            dtype)
        v = dequantize_rows(v, _pool_gather(pool["v_s"], layer, table),
                            dtype)
    return (k.astype(dtype), v.astype(dtype),
            _pool_gather(pool["pos"], layer, table)[..., 0])


def _gqa_decode_paged(p, x, pool, pos, cfg: AttnConfig, eps,
                      paged: PagedKV, write_mask, layer):
    """One-token GQA decode against the paged pool: write the new
    token's K/V into the lane's (page, slot) target of ``layer``, then
    attend over the lane's pages of that layer."""
    b = x.shape[0]
    q, k, v = _gqa_qkv_decode(p, x, pos, cfg, eps)
    new_pool = _pool_commit(
        pool, layer, _decode_targets(paged, write_mask, pool["k"].shape[-1]),
        _kv_rows(pool, k, v, pos[:, None]))

    table = paged.page_table                                  # (B, maxp)
    scale = cfg.softmax_scale or 1.0 / math.sqrt(cfg.head_dim)
    if _PAGED_KERNEL.get() and "k_s" not in pool:
        from repro.kernels import ops as kops
        out = kops.paged_attention(
            q[:, 0], new_pool["k"], new_pool["v"], new_pool["pos"],
            table, pos.astype(jnp.int32), scale=scale, window=cfg.window,
            layer=layer)
        out = out[:, None]                                    # (B,1,H,hd)
    else:
        k_full, v_full, pos_full = _kv_history(new_pool, layer, table,
                                               q.dtype)
        mask = causal_mask(pos[:, None], pos_full, cfg.window)
        mask &= pos_full[:, None, :] >= 0
        out = _sdpa(q, k_full, v_full, mask, scale)
    y = out.reshape(b, 1, -1) @ p["wo"]
    return y, new_pool


def attn_prefill_chunk(p: dict, x: jax.Array, pool: dict,
                       cfg: AttnConfig, eps: float, table: jax.Array,
                       chunk: PrefillChunk, layer=0):
    """One prefill CHUNK against ``layer`` of the paged pool (DESIGN.md
    §9): compute the chunk's q/k/v, write K/V into the per-token (page,
    slot) targets, then attend over the lane's page-table history
    (committed by earlier chunks — or shared prefix pages, which is why
    prefix-cache hits can skip their chunks entirely) PLUS the chunk's
    own in-flight keys, causally.

    The in-flight self-attention deliberately reads the ACTIVATION-dtype
    k/v (not the pool round-trip): a chunk covering the whole prompt
    then computes exactly what `attn_forward` computes, so the
    stop-the-world admission path stays the bit-reference.  History
    reads are clipped to ``kpos < chunk.start`` so the chunk's own
    just-written positions are attended exactly once (in-flight).

    x (B, C, D); pool: a layer-stacked pool (L, P, ...) whose ``layer``
    this is; table (B, maxp) i32 page table; returns (y (B, C, D),
    new_pool).  MLA segments take `_mla_prefill_chunk`.
    """
    if cfg.mla is not None:
        return _mla_prefill_chunk(p, x, pool, cfg, eps, table, chunk, layer)
    b, c, _ = x.shape
    ps = pool["k"].shape[-1]
    rpos = jnp.maximum(chunk.pos, 0)          # rope of pad rows: masked
    q = _split_heads(x @ p["wq"], cfg.n_heads, cfg.head_dim)
    k = _split_heads(x @ p["wk"], cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(x @ p["wv"], cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm({"scale": p["q_norm"]}, q, eps)
        k = rms_norm({"scale": p["k_norm"]}, k, eps)
    cos, sin = rope_cos_sin(rpos, cfg.head_dim, cfg.rope_theta, cfg.yarn)
    q = rope(q, cos, sin)
    k = rope(k, cos, sin)

    # write targets: prefix-cache hits / pad rows / inactive lanes write
    # nothing; a chunk's rows span at most n_win of its lane's pages
    live = chunk.active[:, None] & (chunk.pos >= 0) \
        & (chunk.dest_page != _GARBAGE_PAGE)
    n_win = (c + ps - 2) // ps + 1
    new_pool = _pool_commit(
        pool, layer,
        pool_targets(chunk.dest_page, chunk.dest_slot[:, 0], live, n_win,
                     ps),
        _kv_rows(pool, k, v, chunk.pos))

    scale = cfg.softmax_scale or 1.0 / math.sqrt(cfg.head_dim)
    if _PAGED_KERNEL.get() and "k_s" not in pool:
        from repro.kernels import ops as kops
        out = kops.paged_prefill(
            q, new_pool["k"], new_pool["v"], new_pool["pos"], table,
            chunk.pos, chunk.start, k, v, chunk.pos, scale=scale,
            window=cfg.window, layer=layer)
    else:
        k_hist, v_hist, pos_hist = _kv_history(new_pool, layer, table,
                                               q.dtype)
        hist_ok = (pos_hist >= 0) & (pos_hist < chunk.start[:, None])
        k_all = jnp.concatenate([k_hist, k], axis=1)
        v_all = jnp.concatenate([v_hist, v], axis=1)
        pos_all = jnp.concatenate([pos_hist, chunk.pos], axis=1)
        ok_all = jnp.concatenate([hist_ok, chunk.pos >= 0], axis=1)
        mask = causal_mask(chunk.pos, pos_all, cfg.window) \
            & ok_all[:, None, :]
        out = _sdpa(q, k_all, v_all, mask, scale)
    y = out.reshape(b, c, -1) @ p["wo"]
    return y, new_pool


def attn_decode(p: dict, x: jax.Array, cache: dict, pos: jax.Array,
                cfg: AttnConfig, eps: float = 1e-5,
                paged: PagedKV | None = None, write_mask=None, layer=0):
    """One-token decode against the ring-buffer cache, or — when a
    `PagedKV` handle is given — against the paged KV pool.

    Args:
      x: (B, 1, D) current token activations.
      cache: ring {"k","v": (B,C,Hkv,hd), "pos": (B,C)} or a
        layer-stacked paged pool {"k","v": (L,P,Hkv,hd,page), "pos":
        (L,P,1,page)}, of which this is ``layer``.
      pos: (B,) absolute position of the new token.
      paged: page table + this token's write target (paged mode only).
      write_mask: (B,) lanes whose write should land (paged mode; masked
        lanes write nothing — ring callers mask via the engine's
        `_mask_lane_writes` instead).
      layer: the pool's layer (paged mode; an i32 scalar, traced or not).

    Returns (y, new_cache) — in paged mode the whole stacked pool.
    """
    if cfg.mla is not None:
        return _mla_decode(p, x, cache, pos, cfg, eps, paged, write_mask,
                           layer)
    if paged is not None:
        return _gqa_decode_paged(p, x, cache, pos, cfg, eps, paged,
                                 write_mask, layer)
    b, _, d = x.shape
    c = cache["k"].shape[1]
    q, k, v = _gqa_qkv_decode(p, x, pos, cfg, eps)

    slot = (pos % c).astype(jnp.int32)                       # ring write
    bidx = jnp.arange(b)
    new_cache = dict(cache)
    if "k_s" in cache:  # int8 cache path (models.quant)
        from repro.models.quant import dequantize_rows, quantize_rows
        kq, ks = quantize_rows(k[:, 0])
        vq, vs = quantize_rows(v[:, 0])
        new_cache["k"] = cache["k"].at[bidx, slot].set(kq)
        new_cache["v"] = cache["v"].at[bidx, slot].set(vq)
        new_cache["k_s"] = cache["k_s"].at[bidx, slot].set(ks)
        new_cache["v_s"] = cache["v_s"].at[bidx, slot].set(vs)
        k_full = dequantize_rows(new_cache["k"], new_cache["k_s"], q.dtype)
        v_full = dequantize_rows(new_cache["v"], new_cache["v_s"], q.dtype)
    else:
        new_cache["k"] = cache["k"].at[bidx, slot].set(
            k[:, 0].astype(cache["k"].dtype))
        new_cache["v"] = cache["v"].at[bidx, slot].set(
            v[:, 0].astype(cache["v"].dtype))
        k_full = new_cache["k"].astype(q.dtype)
        v_full = new_cache["v"].astype(q.dtype)
    new_pos = cache["pos"].at[bidx, slot].set(pos.astype(jnp.int32))
    new_cache["pos"] = new_pos

    mask = causal_mask(pos[:, None], new_pos, cfg.window)    # (B,1,C)
    mask &= new_pos[:, None, :] >= 0
    scale = cfg.softmax_scale or 1.0 / math.sqrt(cfg.head_dim)
    out = _sdpa(q, k_full, v_full, mask, scale)
    y = out.reshape(b, 1, -1) @ p["wo"]
    return y, new_cache


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# --------------------------------------------------------------------------

def _mla_q(p, x, cfg: AttnConfig, eps):
    m = cfg.mla
    if m.q_lora_rank:
        cq = rms_norm({"scale": p["q_norm"]}, x @ p["w_dq"], eps)
        q = cq @ p["wq"]
    else:
        q = x @ p["wq"]
    q = q.reshape(*x.shape[:-1], cfg.n_heads,
                  m.qk_nope_head_dim + m.qk_rope_head_dim)
    return q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]


def _mla_latent(p, x, positions, cfg: AttnConfig, eps):
    """What the latent cache stores for x (..., S, D) at positions
    (..., S): c_kv (..., S, lora) after ``kv_norm`` and the shared
    k_rope (..., S, rope) after rotary; and the rotary's cos/sin."""
    m = cfg.mla
    dkv = x @ p["w_dkv"]
    c_kv = rms_norm({"scale": p["kv_norm"]}, dkv[..., :m.kv_lora_rank], eps)
    cos, sin = rope_cos_sin(positions, m.qk_rope_head_dim, cfg.rope_theta,
                            cfg.yarn)
    k_rope = rope(dkv[..., None, m.kv_lora_rank:], cos, sin)[..., 0, :]
    return c_kv, k_rope, cos, sin


def _mla_scale(cfg: AttnConfig) -> float:
    m = cfg.mla
    return cfg.softmax_scale or 1.0 / math.sqrt(
        m.qk_nope_head_dim + m.qk_rope_head_dim)


def _mla_forward(p, x, positions, cfg: AttnConfig, eps):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope = _mla_q(p, x, cfg, eps)
    c_kv, k_rope, cos, sin = _mla_latent(p, x, positions, cfg, eps)
    q_rope = rope(q_rope, cos, sin)

    k_nope = (c_kv @ p["w_uk"]).reshape(b, s, h, m.qk_nope_head_dim)
    v = (c_kv @ p["w_uv"]).reshape(b, s, h, m.v_head_dim)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None],
                                  (b, s, h, m.qk_rope_head_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = _mla_scale(cfg)
    if s >= _CHUNK_THRESHOLD and s % _Q_CHUNK == 0:
        out = _sdpa_chunked(q, k, v, positions, positions, cfg.window, scale)
    else:
        mask = causal_mask(positions, positions, cfg.window)
        out = _sdpa(q, k, v, mask, scale)
    y = out.reshape(b, s, -1) @ p["wo"]
    return y, {"c_kv": c_kv, "k_rope": k_rope}


def _latent_rows(cache: dict, c_kv, k_rope) -> dict:
    """The latent rows a write stores (..., S, *): in the cache's dtype,
    or int8 with per-position scales under models.quant."""
    if "c_kv_s" in cache:
        from repro.models.quant import quantize_rows
        cq, cs = quantize_rows(c_kv)
        rq, rs = quantize_rows(k_rope)
        return {"c_kv": cq, "c_kv_s": cs, "k_rope": rq, "k_rope_s": rs}
    return {"c_kv": c_kv.astype(cache["c_kv"].dtype),
            "k_rope": k_rope.astype(cache["k_rope"].dtype)}


def _latent_history(pool: dict, layer, table):
    """The jnp gather path's view of lanes' pages of ``layer``: c_kv
    (B, T, lora), k_rope (B, T, rope) and positions (B, T), T = maxp *
    ps; int8 pools gather the lanes' pages FIRST, then dequantize only
    those (never the whole pool)."""
    ckv = _pool_gather(pool["c_kv"], layer, table)
    krope = _pool_gather(pool["k_rope"], layer, table)
    if "c_kv_s" in pool:
        from repro.models.quant import dequantize_rows
        ckv = dequantize_rows(
            ckv, _pool_gather(pool["c_kv_s"], layer, table)[..., 0])
        krope = dequantize_rows(
            krope, _pool_gather(pool["k_rope_s"], layer, table)[..., 0])
    return ckv, krope, _pool_gather(pool["pos"], layer, table)[..., 0]


def _mla_absorbed(p, q_nope, q_rope, ckv, krope, mask, cfg: AttnConfig,
                  dtype):
    """Attention in the compressed kv_lora space, W_uk absorbed into the
    query and W_uv applied after (DESIGN.md §4, §9).  q_nope (..., Q,
    H, nope), q_rope (..., Q, H, rope) with rotary applied; ckv (..., T,
    lora), krope (..., T, rope); mask (..., Q, T).  Returns (..., Q, H,
    v_head_dim)."""
    m, h = cfg.mla, cfg.n_heads
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_c = jnp.einsum("...qhn,rhn->...qhr", q_nope, w_uk)
    scores = jnp.einsum("...qhr,...tr->...hqt", q_c, ckv.astype(q_c.dtype))
    scores += jnp.einsum("...qhe,...te->...hqt", q_rope,
                         krope.astype(q_rope.dtype))
    logits = jnp.where(mask[..., None, :, :],
                       scores.astype(jnp.float32) * _mla_scale(cfg), -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(dtype)
    ctx = jnp.einsum("...hqt,...tr->...qhr", w, ckv.astype(w.dtype))
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    return jnp.einsum("...qhr,rhv->...qhv", ctx, w_uv)


@jax.named_scope("mla_chunk")
def _mla_prefill_chunk(p, x, pool, cfg: AttnConfig, eps, table,
                       chunk: PrefillChunk, layer):
    """One prefill chunk of MLA against ``layer`` of the latent pool
    (DESIGN.md §9): write the chunk's latent rows (c_kv after kv_norm,
    k_rope after rotary) into their (page, slot) targets, then attend
    causally over the lane's pages, its own rows included, with
    positions from the pool's ``pos`` — absorbed, as decode attends.
    Pad rows, prefix-cache hits and inactive lanes write nothing; each
    lane attends on its own (``lax.map``) and a lane that prefills
    nothing this step is skipped, so the step reads the latent pages of
    the prefilling lanes only."""
    b, c, _ = x.shape
    ps = pool["c_kv"].shape[-1]
    q_nope, q_rope = _mla_q(p, x, cfg, eps)
    c_kv, k_rope, cos, sin = _mla_latent(p, x, jnp.maximum(chunk.pos, 0),
                                         cfg, eps)
    q_rope = rope(q_rope, cos, sin)
    live = chunk.active[:, None] & (chunk.pos >= 0) \
        & (chunk.dest_page != _GARBAGE_PAGE)
    n_win = (c + ps - 2) // ps + 1
    rows = dict(_latent_rows(pool, c_kv, k_rope),
                pos=chunk.pos.astype(jnp.int32))
    new_pool = _pool_commit(
        pool, layer,
        pool_targets(chunk.dest_page, chunk.dest_slot[:, 0], live, n_win,
                     ps), rows)

    def attend(args):
        qn, qr, tab, qpos, _ = args
        ckv, krope, kpos = _latent_history(new_pool, layer, tab[None])
        mask = causal_mask(qpos, kpos[0], cfg.window) & (kpos >= 0)
        return _mla_absorbed(p, qn, qr, ckv[0], krope[0], mask, cfg,
                             x.dtype)

    def skip(args):
        return jnp.zeros((c, cfg.n_heads, cfg.mla.v_head_dim), x.dtype)

    out = jax.lax.map(lambda a: jax.lax.cond(a[-1], attend, skip, a),
                      (q_nope, q_rope, table, chunk.pos, chunk.active))
    y = out.reshape(b, c, -1) @ p["wo"]
    return y, new_pool


@jax.named_scope("mla_decode")
def _mla_decode(p, x, cache, pos, cfg: AttnConfig, eps,
                paged: PagedKV | None = None, write_mask=None, layer=0):
    """Absorbed-matmul MLA decode: attention runs in the compressed
    kv_lora space — the cache stays (B, C, lora + rope) (ring) or
    (P, lora + rope, page) per layer (paged), which is the whole point
    of MLA (DESIGN.md §4)."""
    b = x.shape[0]
    q_nope, q_rope = _mla_q(p, x, cfg, eps)                  # (B,1,H,*)
    c_new, k_rope_new, cos, sin = _mla_latent(p, x, pos[:, None], cfg, eps)
    q_rope = rope(q_rope, cos, sin)
    rows = _latent_rows(cache, c_new[:, 0], k_rope_new[:, 0])
    if paged is not None:
        # write the row into ``layer`` of the pool, then gather the
        # lanes' pages back to the per-lane (B, T, ...) layout
        rows["pos"] = pos.astype(jnp.int32)
        new_cache = _pool_commit(
            cache, layer,
            _decode_targets(paged, write_mask, cache["c_kv"].shape[-1]),
            {n: r[:, None] for n, r in rows.items()})
        ckv, krope, new_pos = _latent_history(new_cache, layer,
                                              paged.page_table)
    else:
        c = cache["c_kv"].shape[1]
        bidx, slot = jnp.arange(b), (pos % c).astype(jnp.int32)
        new_cache = {name: cache[name].at[bidx, slot].set(r)
                     for name, r in rows.items()}
        new_pos = cache["pos"].at[bidx, slot].set(pos.astype(jnp.int32))
        new_cache["pos"] = new_pos
        ckv, krope = new_cache["c_kv"], new_cache["k_rope"]
        if "c_kv_s" in cache:
            from repro.models.quant import dequantize_rows
            ckv = dequantize_rows(ckv, new_cache["c_kv_s"])
            krope = dequantize_rows(krope, new_cache["k_rope_s"])

    mask = causal_mask(pos[:, None], new_pos, cfg.window)     # (B,1,T)
    mask &= new_pos[:, None, :] >= 0
    out = _mla_absorbed(p, q_nope, q_rope, ckv, krope, mask, cfg, x.dtype)
    y = out.reshape(b, 1, -1) @ p["wo"]
    return y, new_cache
