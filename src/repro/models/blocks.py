"""Block assembly: pre-norm residual blocks for attn / ssm / hybrid mixers
with dense or MoE MLPs, plus ring-cache construction after prefill."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, moe as moe_lib, mlp as mlp_lib, ssm as ssm_lib
from repro.models.common import rms_norm, rms_norm_def
from repro.models.config import BlockConfig
from repro.models.param import ParamDef

__all__ = ["block_defs", "block_forward", "block_decode",
           "block_prefill_chunk", "cache_defs", "build_ring_cache"]


def block_defs(cfg: BlockConfig, d_model: int) -> dict:
    defs: dict = {"norm1": rms_norm_def(d_model)}
    if cfg.mixer in ("attn", "hybrid"):
        defs["attn"] = attention.attn_defs(cfg.attn, d_model)
    if cfg.mixer in ("ssm", "hybrid"):
        defs["ssm"] = ssm_lib.ssm_defs(cfg.ssm, d_model)
    if cfg.mixer == "hybrid":
        # Hymba: per-branch output norms, fused by averaging (DESIGN.md §4).
        defs["attn_out_norm"] = rms_norm_def(d_model)
        defs["ssm_out_norm"] = rms_norm_def(d_model)
    if cfg.mlp == "dense":
        defs["norm2"] = rms_norm_def(d_model)
        defs["mlp"] = mlp_lib.mlp_defs(d_model, cfg.d_ff, cfg.act)
    elif cfg.mlp == "moe":
        defs["norm2"] = rms_norm_def(d_model)
        defs["moe"] = moe_lib.moe_defs(cfg.moe, d_model, cfg.act)
    return defs


def cache_defs(cfg: BlockConfig, d_model: int, batch: int,
               cache_len: int) -> dict:
    """(shape, dtype) spec tree for one block's decode cache."""
    out: dict = {}
    if cfg.mixer in ("attn", "hybrid"):
        out["attn"] = attention.init_cache_defs(cfg.attn, batch, cache_len)
    if cfg.mixer in ("ssm", "hybrid"):
        out["ssm"] = ssm_lib.ssm_state_defs(cfg.ssm, d_model, batch)
    return out


def _mixer_full(p, xn, positions, cfg: BlockConfig, eps, use_flash,
                use_ssd_kernel):
    """Full-sequence mixer.  Returns (y, cache_entry)."""
    if cfg.mixer == "attn":
        y, kv = attention.attn_forward(p["attn"], xn, positions, cfg.attn,
                                       eps, use_flash)
        return y, {"attn_kv": kv}
    if cfg.mixer == "ssm":
        y, st = ssm_lib.ssm_forward(p["ssm"], xn, cfg.ssm, eps,
                                    use_ssd_kernel)
        return y, {"ssm": st}
    # hybrid: parallel attention + SSD heads on the same normed input
    ya, kv = attention.attn_forward(p["attn"], xn, positions, cfg.attn,
                                    eps, use_flash)
    ys, st = ssm_lib.ssm_forward(p["ssm"], xn, cfg.ssm, eps, use_ssd_kernel)
    y = 0.5 * (rms_norm(p["attn_out_norm"], ya, eps)
               + rms_norm(p["ssm_out_norm"], ys, eps))
    return y, {"attn_kv": kv, "ssm": st}


def _mlp(p: dict, x: jax.Array, cfg: BlockConfig, eps: float, live=None):
    """The block's residual MLP.  Returns (x, aux).  For MoE blocks,
    ``live`` (rows of x, bool) selects the serving expert layer
    (`moe.moe_serve`, dropless: only live rows route, and aux holds its
    counters); without it they take the training dispatch (aux holds
    its losses)."""
    if cfg.mlp == "dense":
        return x + mlp_lib.mlp_forward(p["mlp"], rms_norm(p["norm2"], x, eps),
                                       cfg.act), {}
    if cfg.mlp == "moe":
        xn = rms_norm(p["norm2"], x, eps)
        if live is None:
            y, aux = moe_lib.moe_forward(p["moe"], xn, cfg.moe, cfg.act)
        else:
            y, aux = moe_lib.moe_serve(p["moe"], xn, cfg.moe, cfg.act, live)
        return x + y, aux
    return x, {}


def block_forward(p: dict, x: jax.Array, positions: jax.Array,
                  cfg: BlockConfig, eps: float = 1e-5,
                  use_flash: bool = False, use_ssd_kernel: bool = False,
                  serve: bool = False):
    """Train/prefill pass.  Returns (y, cache_entry, aux).  ``serve``
    (the serving prefill) routes MoE rows through the dropless serving
    layer, so a prompt's experts are those its decode would use."""
    xn = rms_norm(p["norm1"], x, eps)
    mix, cache = _mixer_full(p, xn, positions, cfg, eps, use_flash,
                             use_ssd_kernel)
    live = jnp.ones(x.shape[:-1], bool) if serve and cfg.mlp == "moe" \
        else None
    x, aux = _mlp(p, x + mix, cfg, eps, live)
    return x, cache, aux


def block_decode(p: dict, x: jax.Array, cache: dict, pos: jax.Array,
                 cfg: BlockConfig, eps: float = 1e-5,
                 paged=None, write_mask=None, layer=0):
    """One-token step.  x (B,1,D); returns (y, new_cache, aux): MoE
    blocks route through the dropless serving layer, the lanes of
    ``write_mask`` only (every lane without it), and aux holds its
    counters.

    ``paged``/``write_mask`` switch the attention cache to the paged KV
    pool (attention.PagedKV): ``cache["attn"]`` is then the segment's
    layer-stacked pool, of which this block is ``layer``.  SSM state
    stays lane-indexed either way — its per-lane masking is the
    engine's job.
    """
    xn = rms_norm(p["norm1"], x, eps)
    new_cache: dict = {}
    if cfg.mixer == "attn":
        mix, new_cache["attn"] = attention.attn_decode(
            p["attn"], xn, cache["attn"], pos, cfg.attn, eps,
            paged=paged, write_mask=write_mask, layer=layer)
    elif cfg.mixer == "ssm":
        mix, new_cache["ssm"] = ssm_lib.ssm_decode(
            p["ssm"], xn, cache["ssm"], cfg.ssm, eps)
    else:
        ya, new_cache["attn"] = attention.attn_decode(
            p["attn"], xn, cache["attn"], pos, cfg.attn, eps,
            paged=paged, write_mask=write_mask, layer=layer)
        ys, new_cache["ssm"] = ssm_lib.ssm_decode(
            p["ssm"], xn, cache["ssm"], cfg.ssm, eps)
        mix = 0.5 * (rms_norm(p["attn_out_norm"], ya, eps)
                     + rms_norm(p["ssm_out_norm"], ys, eps))
    live = None
    if cfg.mlp == "moe":
        live = jnp.ones(x.shape[:-1], bool) if write_mask is None \
            else jnp.broadcast_to(write_mask[:, None], x.shape[:-1])
    x, aux = _mlp(p, x + mix, cfg, eps, live)
    return x, new_cache, aux


def block_prefill_chunk(p: dict, x: jax.Array, cache: dict,
                        cfg: BlockConfig, eps: float, table: jax.Array,
                        chunk, layer=0):
    """One prefill CHUNK through a block against ``layer`` of the
    segment's layer-stacked paged pool (DESIGN.md §9).  x (B, C, D);
    returns (y, new_cache, aux): MoE blocks route the chunk's live rows
    (active lanes, real positions) only, and aux holds their counters.
    Only attention mixers (GQA and MLA) are chunkable — SSM state is
    inherently sequential over the whole prompt, so ssm/hybrid models
    admit through the stop-the-world prefill path (gated at
    EngineStepper construction).
    """
    if cfg.mixer != "attn":
        raise NotImplementedError(
            f"chunked prefill supports attention blocks only, not "
            f"{cfg.mixer!r}")
    xn = rms_norm(p["norm1"], x, eps)
    mix, new_attn = attention.attn_prefill_chunk(
        p["attn"], xn, cache["attn"], cfg.attn, eps, table, chunk, layer)
    live = chunk.active[:, None] & (chunk.pos >= 0) \
        if cfg.mlp == "moe" else None
    x, aux = _mlp(p, x + mix, cfg, eps, live)
    return x, {"attn": new_attn}, aux


def build_ring_cache(cache_entry: dict, positions: jax.Array,
                     cfg: BlockConfig, cache_len: int) -> dict:
    """Convert prefill outputs into the fixed-size ring decode cache.

    Takes the last `cache_len` positions and scatters them at slot
    pos % cache_len — for full prefixes this is the identity layout, for
    windowed attention it reproduces the steady-state ring.
    """
    out: dict = {}
    if "attn_kv" in cache_entry:
        kv = cache_entry["attn_kv"]
        pos_tail = positions[:, -cache_len:]
        slots = (pos_tail % cache_len).astype(jnp.int32)      # (B, C)
        b = pos_tail.shape[0]
        bidx = jnp.arange(b)[:, None]

        def scatter(src):
            tail = src[:, -cache_len:]
            buf = jnp.zeros((b, cache_len) + tail.shape[2:],
                            jnp.bfloat16)
            return buf.at[bidx, slots].set(tail.astype(jnp.bfloat16))

        entry = {k: scatter(v) for k, v in kv.items()}
        from repro.models.quant import int8_enabled, quantize_rows
        if int8_enabled():
            for name in list(entry):
                q, s = quantize_rows(entry[name])
                entry[name] = q
                entry[name + "_s"] = s
        pos_buf = jnp.full((b, cache_len), -1, jnp.int32)
        entry["pos"] = pos_buf.at[bidx, slots].set(
            pos_tail.astype(jnp.int32))
        out["attn"] = entry
    if "ssm" in cache_entry:
        out["ssm"] = cache_entry["ssm"]
    return out
