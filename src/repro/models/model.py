"""Model assembly: segments of scanned blocks with early-exit ramps.

Public API (all pure functions over a params pytree):
  * ``model_defs(cfg)``       — ParamDef tree (shapes + logical axes).
  * ``forward_train(...)``    — full pass, EE multi-ramp loss (train_step).
  * ``prefill(...)``          — full pass, builds ring KV caches + per-ramp
                                confidences of the last position.
  * ``decode_step(...)``      — one-token step over all segments.
  * ``decode_segment(...)``   — one segment only (the serving engine's unit
                                of work: run segment, consult T-Tamer
                                if-stop table, maybe exit — DESIGN.md §2).

Ramp heads are a per-ramp RMSNorm + the shared (tied) unembedding — the
"logit lens" ramp, cheap in parameters; per-node cost c_i for T-Tamer is
the segment's FLOPs (benchmarks/flops.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention, blocks
from repro.models.common import embed_def, rms_norm, rms_norm_def
from repro.models.config import ModelConfig, Segment
from repro.models.param import ParamDef
from repro.sharding.ctx import constrain_batch

__all__ = ["model_defs", "forward_train", "prefill", "decode_step",
           "decode_segment", "prefill_chunk_segment", "cache_specs",
           "paged_cache_specs", "unembed", "decode_unroll", "ramp_readout"]

# Decode-layer execution (perf hillclimb lever, EXPERIMENTS.md §Perf):
# scan (default) keeps HLO small; unrolled decode removes the per-step
# dynamic-slice copies of the stacked layer weights — the standard
# production choice for serving steps.
import contextlib
import contextvars

_DECODE_UNROLL = contextvars.ContextVar("repro_decode_unroll", default=False)


@contextlib.contextmanager
def decode_unroll(on: bool = True):
    tok = _DECODE_UNROLL.set(on)
    try:
        yield
    finally:
        _DECODE_UNROLL.reset(tok)


# --------------------------------------------------------------------------
# Parameter definitions
# --------------------------------------------------------------------------

def _stack_defs(defs, n: int):
    return jax.tree.map(
        lambda d: dataclasses.replace(d, shape=(n,) + d.shape,
                                      axes=("layers",) + d.axes,
                                      fan_axis=d.fan_axis + 1),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def model_defs(cfg: ModelConfig) -> dict:
    defs: dict = {}
    if cfg.input_mode in ("tokens", "multimodal"):
        defs["embed"] = embed_def(cfg.vocab, cfg.d_model)
    elif cfg.tie_embeddings:
        # embeds-in models still need the output table
        defs["embed"] = embed_def(cfg.vocab, cfg.d_model)
    segs = []
    for seg in cfg.segments:
        sd: dict = {"blocks": _stack_defs(
            blocks.block_defs(seg.block, cfg.d_model), seg.n_layers)}
        if seg.ramp:
            sd["ramp"] = {"norm": rms_norm_def(cfg.d_model)}
        segs.append(sd)
    defs["segments"] = segs
    defs["final_norm"] = rms_norm_def(cfg.d_model)
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((cfg.d_model, cfg.vocab),
                                   ("embed", "vocab"))
    return defs


def unembed(params: dict, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return h @ params["embed"]["table"].T.astype(h.dtype)
    return h @ params["unembed"].astype(h.dtype)


def ramp_readout(params, cfg: ModelConfig, h: jax.Array,
                 segment: int | None = None):
    """The shared ramp / final-head readout (DESIGN.md §2): per-node
    RMSNorm, tied unembedding, and the T-Tamer loss proxy
    ``ell = 1 - max softmax prob`` (paper §6 / App. D.2).

    ``h`` is the RAW residual-stream hidden at the readout point, shape
    ``(..., D)``; ``segment`` selects that segment's ramp norm (``None``
    -> the final head norm).  Returns ``(logits (..., V), ell (...))``.
    One implementation feeds training (ramp CE), calibration (prefill
    node losses), and both serving engines, so the calibrated tables see
    exactly the quantity the online loop measures.
    """
    if segment is None:
        norm = params["final_norm"]
    else:
        norm = params["segments"][segment]["ramp"]["norm"]
    hn = rms_norm(norm, h, cfg.norm_eps)
    logits = unembed(params, cfg, hn)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return logits, 1.0 - p.max(axis=-1)


def _embed_inputs(params, cfg: ModelConfig, batch: dict):
    """Returns (x (B,S,D), positions (B,S))."""
    if cfg.input_mode == "tokens":
        x = params["embed"]["table"][batch["tokens"]]
    elif cfg.input_mode == "embeds":
        x = batch["embeds"]
    elif cfg.input_mode == "multimodal":
        tok = params["embed"]["table"][batch["tokens"]]
        x = jnp.concatenate([batch["image_embeds"].astype(tok.dtype), tok],
                            axis=1)
    else:
        raise ValueError(cfg.input_mode)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return constrain_batch(x), positions


def _merge_aux(total: dict, aux_stack: dict) -> dict:
    for k, v in aux_stack.items():
        total[k] = total.get(k, 0.0) + jnp.sum(v)
    return total


# --------------------------------------------------------------------------
# Full-sequence passes
# --------------------------------------------------------------------------

def _run_segments(params, cfg: ModelConfig, x, positions, *,
                  want_cache: bool, cache_len: int | None,
                  remat: bool, use_flash: bool, use_ssd_kernel: bool):
    """Returns (final_hidden, ramp_hiddens, caches, aux)."""
    ramp_hiddens = []
    caches = []
    aux: dict = {}
    for si, seg in enumerate(cfg.segments):
        p_seg = params["segments"][si]["blocks"]

        if want_cache:
            def body(h, p_layer, seg=seg):
                y, cache, a = blocks.block_forward(
                    p_layer, h, positions, seg.block, cfg.norm_eps,
                    use_flash, use_ssd_kernel, serve=True)
                ring = blocks.build_ring_cache(cache, positions, seg.block,
                                               cache_len)
                return y, (ring, a)
        else:
            def body(h, p_layer, seg=seg):
                y, _, a = blocks.block_forward(
                    p_layer, h, positions, seg.block, cfg.norm_eps,
                    use_flash, use_ssd_kernel)
                return y, a

        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        if want_cache:
            x, (ring_stack, aux_stack) = jax.lax.scan(body, x, p_seg)
            caches.append(ring_stack)
        else:
            x, aux_stack = jax.lax.scan(body, x, p_seg)
        x = constrain_batch(x)  # re-anchor residual-stream sharding
        aux = _merge_aux(aux, aux_stack)
        if seg.ramp:
            # RAW hidden; `ramp_readout` applies the per-ramp norm + head
            ramp_hiddens.append((si, x))
    return x, ramp_hiddens, caches, aux


def _xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE over valid (label >= 0) positions.  logits (B,S,V)."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(
        logits.astype(jnp.float32),
        jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    valid = labels >= 0
    ce = jnp.where(valid, lse - ll, 0.0)
    return ce.sum() / jnp.maximum(valid.sum(), 1)


def forward_train(params, cfg: ModelConfig, batch: dict, *,
                  ramp_loss_weight: float = 0.3, remat: bool = True,
                  use_flash: bool = False, use_ssd_kernel: bool = False):
    """EE training objective: CE(final) + w * mean_r CE(ramp_r) + MoE aux.

    batch: {"tokens"/"embeds"/"image_embeds", "labels" (B, S_total)}.
    Returns (loss, metrics dict).
    """
    x, positions = _embed_inputs(params, cfg, batch)
    final, ramps, _, aux = _run_segments(
        params, cfg, x, positions, want_cache=False, cache_len=None,
        remat=remat, use_flash=use_flash, use_ssd_kernel=use_ssd_kernel)
    labels = batch["labels"]
    loss = _xent(ramp_readout(params, cfg, final)[0], labels)
    metrics = {"ce_final": loss}
    if ramps:
        ramp_ce = 0.0
        for ri, (si, h) in enumerate(ramps):
            ce = _xent(ramp_readout(params, cfg, h, segment=si)[0], labels)
            metrics[f"ce_ramp{ri}"] = ce
            ramp_ce += ce
        loss = loss + ramp_loss_weight * ramp_ce / len(ramps)
    for k, v in aux.items():
        metrics[k] = v
        loss = loss + v
    metrics["loss"] = loss
    return loss, metrics


def prefill(params, cfg: ModelConfig, batch: dict, cache_len: int, *,
            use_flash: bool = False, use_ssd_kernel: bool = False):
    """Serving prefill: returns (last_logits (B,V), caches, ramp_losses
    (B, n_nodes), next_pos (B,)).  n_nodes = ramps + final (the T-Tamer
    line; the final head is the last node)."""
    x, positions = _embed_inputs(params, cfg, batch)
    final, ramps, caches, _ = _run_segments(
        params, cfg, x, positions, want_cache=True, cache_len=cache_len,
        remat=False, use_flash=use_flash, use_ssd_kernel=use_ssd_kernel)
    node_losses = [ramp_readout(params, cfg, h[:, -1, :], segment=si)[1]
                   for si, h in ramps]
    logits, final_loss = ramp_readout(params, cfg, final[:, -1, :])
    node_losses.append(final_loss)
    next_pos = positions[:, -1] + 1
    return logits, caches, jnp.stack(node_losses, axis=1), next_pos


# --------------------------------------------------------------------------
# Decode
# --------------------------------------------------------------------------

def _layer_loop(body, x, p_seg, cache_seg, n_layers: int, pooled: bool,
                unroll: bool = False):
    """Run ``body(h, p_layer, layer_cache, li) -> (h, new_layer_cache,
    aux)`` over a segment's layers; returns (h, new_cache, aux summed
    over the layers).  Lane-indexed leaves are sliced per layer
    and restacked; with ``pooled`` the paged pool (``"attn"``) stays
    whole in the loop's carry instead — each layer writes its rows into
    it in place and reads its own layer by index, so no layer's pool is
    ever sliced out or restacked (DESIGN.md §8)."""
    pool = cache_seg.get("attn") if pooled else None
    laned = {k: v for k, v in cache_seg.items()
             if not (pooled and k == "attn")}

    def one(h, pool, p_layer, li, lane_c):
        c = dict(lane_c)
        if pool is not None:
            c["attn"] = pool
        h, nc, aux = body(h, p_layer, c, li)
        nc = dict(nc)
        return h, (nc.pop("attn") if pool is not None else None), (nc, aux)

    if unroll:
        outs = []
        for li in range(n_layers):
            def pick(a, li=li):
                return a[li]
            x, pool, out = one(x, pool, jax.tree.map(pick, p_seg), li,
                               jax.tree.map(pick, laned))
            outs.append(out)
        new, aux = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    else:
        def step(carry, xs):
            h, pool = carry
            h, pool, out = one(h, pool, *xs)
            return (h, pool), out

        (x, pool), (new, aux) = jax.lax.scan(
            step, (x, pool),
            (p_seg, jnp.arange(n_layers, dtype=jnp.int32), laned))
    new = dict(new)
    if pool is not None:
        new["attn"] = pool
    return x, new, jax.tree.map(lambda a: a.sum(0), aux)


def decode_segment(params, cfg: ModelConfig, si: int, x: jax.Array,
                   cache_seg, pos: jax.Array, paged=None, write_mask=None,
                   readout_scope: str | None = None):
    """Run segment `si` for one token.  x (B,1,D) -> (x', new_cache,
    readout, counts) where readout is None for ramp-less segments and
    otherwise the full `ramp_readout` pair (logits (B,V), loss proxy
    (B,)) — the serving engine consumes both, so the head matmul runs
    exactly once — and counts the expert layer's counters summed over
    the segment's layers (`moe.EXPERT_COUNTS`; empty for a segment
    without experts).  ``readout_scope`` names the readout's
    `jax.named_scope` (the serving engine's ``readout<node>``).

    ``paged`` (attention.PagedKV) + ``write_mask`` route the attention
    layers at the paged KV pool; the per-lane page table and write
    target are shared by every layer (page ids are global)."""
    seg = cfg.segments[si]

    def body(h, p_layer, c, li):
        return blocks.block_decode(p_layer, h, c, pos, seg.block,
                                   cfg.norm_eps, paged=paged,
                                   write_mask=write_mask, layer=li)

    x, new_cache, aux = _layer_loop(
        body, x, params["segments"][si]["blocks"], cache_seg, seg.n_layers,
        paged is not None, unroll=_DECODE_UNROLL.get())
    readout = None
    if seg.ramp:
        with (jax.named_scope(readout_scope) if readout_scope
              else contextlib.nullcontext()):
            readout = ramp_readout(params, cfg, x[:, 0, :], segment=si)
    return x, new_cache, readout, aux


def prefill_chunk_segment(params, cfg: ModelConfig, si: int, x: jax.Array,
                          cache_seg, table: jax.Array, chunk):
    """Run segment ``si`` for one PREFILL CHUNK against the paged pool
    (DESIGN.md §9).  x (B, C, D) -> (x', new_cache, the expert
    counters summed over the layers).  Chunks always run full depth
    (no early exit during prefill: every layer's KV must be
    complete before decode can share the pages), so there is no ramp
    readout here — the engine reads the final head once, on the chunk
    that finishes the prompt."""
    seg = cfg.segments[si]

    def body(h, p_layer, c, li):
        return blocks.block_prefill_chunk(p_layer, h, c, seg.block,
                                          cfg.norm_eps, table, chunk, li)

    x, new_cache, aux = _layer_loop(body, x,
                                    params["segments"][si]["blocks"],
                                    cache_seg, seg.n_layers, True)
    return constrain_batch(x), new_cache, aux


def decode_step(params, cfg: ModelConfig, batch: dict, caches, pos):
    """Full-depth one-token step (the dry-run `serve_step` for decode
    shapes — worst case, no early exit).

    batch: {"tokens": (B,)} or {"embeds": (B, D)}.
    Returns (logits (B,V), new_caches, node_losses (B, n_nodes)).
    """
    if cfg.input_mode in ("tokens", "multimodal"):
        x = params["embed"]["table"][batch["tokens"]][:, None, :]
    else:
        x = batch["embeds"][:, None, :]
    x = constrain_batch(x)
    new_caches = []
    node_losses = []
    for si in range(len(cfg.segments)):
        x, nc, ro, _ = decode_segment(params, cfg, si, x, caches[si], pos)
        new_caches.append(nc)
        if ro is not None:
            node_losses.append(ro[1])
    logits, final_loss = ramp_readout(params, cfg, x[:, 0, :])
    node_losses.append(final_loss)
    return logits, new_caches, jnp.stack(node_losses, axis=1)


def _stack_specs(cd: dict, n_layers: int):
    return jax.tree.map(
        lambda sd: ((n_layers,) + sd[0], sd[1]),
        cd, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))


def cache_specs(cfg: ModelConfig, batch: int, cache_len: int) -> list:
    """(shape, dtype) spec tree for the whole decode cache (per segment,
    stacked over the segment's layers)."""
    return [_stack_specs(
        blocks.cache_defs(seg.block, cfg.d_model, batch, cache_len),
        seg.n_layers) for seg in cfg.segments]


def paged_cache_specs(cfg: ModelConfig, n_lanes: int, n_pages: int,
                      page_size: int) -> list:
    """Spec tree for the PAGED decode cache (DESIGN.md §8): attention
    leaves swap the lane axis for the global page pool and put the
    page-slot axis minor — ``(L, P, *row, page_size)``, the layout the
    paged kernels read (`attention.pool_cache_defs`) — while SSM state
    (no sequence axis to page) stays lane-indexed ``(L, n_lanes, ...)``.
    Leaf names match `cache_specs` so the quant/dtype plumbing is
    shared."""
    out = []
    for seg in cfg.segments:
        laned = blocks.cache_defs(seg.block, cfg.d_model, n_lanes, 1)
        entry = {}
        if "attn" in laned:
            entry["attn"] = attention.pool_cache_defs(
                seg.block.attn, n_pages, page_size)
        if "ssm" in laned:
            entry["ssm"] = laned["ssm"]
        out.append(_stack_specs(entry, seg.n_layers))
    return out
