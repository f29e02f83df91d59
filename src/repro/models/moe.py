"""Mixture-of-Experts layer: top-k routing over the router's experts,
shared experts (DeepSeek-V2), and two dispatches.

  * Training (`moe_forward`): GROUPED, capacity-bounded, sort-based
    dispatch with load-balance + router-z aux losses.
  * Serving (`moe_serve`): DROPLESS.  Every assignment of a live row to
    a held expert is computed, by grouped matrix products over the
    assignments sorted by expert (`jax.lax.ragged_dot`, whose work is
    the routed rows); rows are independent, so a token's output never
    depends on its batch-mates, and rows that are not live (pad rows of
    the chunk sweep, exited or idle lanes) route to no expert.

Expert parallelism (DESIGN.md §4): the config may say this chip holds
experts ``0 .. held_experts - 1`` of the router's ``num_experts``.  The
router keeps every output and its top-k; the layer computes only the
held experts' part of the result, and assignments to the other experts
add nothing (on one chip the layer runs without the exchange that would
bring their rows elsewhere).

Why grouped + sort-based for training (DESIGN.md §3):
  * GShard one-hot dispatch einsums inflate HLO FLOPs by ~E/k and would
    wreck the roofline's useful-compute ratio — we never build them.
  * A single global argsort over B*S*k assignments would force GSPMD to
    emit a distributed sort; instead tokens are routed within GROUPS
    (one group per sequence for full passes, one group for decode).  The
    group axis shards on ("pod","data") so every sort/gather/scatter is
    local to a data shard, and the expert axis of the batched matmuls
    'gecd,edf->gecf' shards on "model" — expert parallelism with zero
    GSPMD surprises.

Training pipeline per group:
  router -> top-k -> stable sort by expert -> position-within-expert ->
  capacity drop -> (E, C) token-id buffer -> gather (E, C, D) ->
  per-expert matmuls -> weighted scatter-add back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import MoEConfig
from repro.models.mlp import mlp_defs, mlp_forward
from repro.models.param import ParamDef
from repro.sharding.ctx import constrain_batch

__all__ = ["moe_defs", "moe_forward", "moe_serve", "moe_route",
           "EXPERT_COUNTS"]

# the per-call counters `moe_serve` returns (int32 scalars)
EXPERT_COUNTS = ("expert_rows", "expert_rows_live", "expert_rows_max")


def ragged_tile_rows(m: int) -> int:
    """Rows of the row tiles in which the TPU's ragged dot computes an
    lhs of ``m`` rows.  Measured on a TPU v5e at DeepSeek-V2-Lite's
    expert shapes: a call's time steps with the (row tile, expert)
    pairs that the experts' sorted rows touch, 512-row tiles for 512
    rows and more (12,288: the chunk sweep), 32-row tiles for 96 (a
    decode batch); rows past the last group cost nothing."""
    return 512 if m >= 512 else 32


def _tile_rows(sizes: jax.Array, m: int) -> jax.Array:
    """Rows the grouped products compute over groups of ``sizes`` laid
    end to end in an lhs of ``m`` rows: every row tile that a non-empty
    group touches is computed whole, once per group."""
    t = ragged_tile_rows(m)
    end = jnp.cumsum(sizes)
    start = end - sizes
    tiles = jnp.where(sizes > 0, (end - 1) // t - start // t + 1, 0)
    return t * tiles.sum(dtype=jnp.int32)


def moe_defs(cfg: MoEConfig, d_model: int, act: str) -> dict:
    e, f = cfg.n_held, cfg.d_ff_expert
    defs = {
        "router": ParamDef((d_model, cfg.num_experts), ("embed", None),
                           scale=0.1),
        "w_up": ParamDef((e, d_model, f), ("experts", "embed", "mlp"),
                         fan_axis=1),
        "w_down": ParamDef((e, f, d_model), ("experts", "mlp", "embed"),
                           fan_axis=1),
    }
    if act == "swiglu":
        defs["w_gate"] = ParamDef((e, d_model, f),
                                  ("experts", "embed", "mlp"), fan_axis=1)
    if cfg.num_shared > 0:
        shared_ff = cfg.d_ff_shared or cfg.num_shared * f
        defs["shared"] = mlp_defs(d_model, shared_ff, act)
    return defs


def _group_shape(b: int, s: int) -> tuple[int, int]:
    """One routing group per sequence for full passes; a single group for
    decode (S == 1), so routing never crosses data shards on the batch."""
    if s == 1:
        return 1, b
    return b, s


def moe_route(p: dict, x: jax.Array, cfg: MoEConfig):
    """Router over all ``num_experts`` in float32 (logits at the highest
    matmul precision, as DeepSeek-V2 computes them), softmax, top-k.
    Returns (logits, probs, gates (..., k) f32, experts (..., k) i32);
    gates are renormalised over the top-k only with ``norm_topk_prob``."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return logits, probs, gates, experts


def _held(cfg: MoEConfig, experts):
    """Whether this chip holds each chosen expert."""
    return experts < cfg.n_held


def moe_serve(p: dict, x: jax.Array, cfg: MoEConfig, act: str,
              live: jax.Array):
    """Dropless serving layer over rows.  x (..., D); live (...) bool
    marks the rows whose tokens route at all.  Returns (y (..., D), the
    `EXPERT_COUNTS` of this call: rows the held experts' grouped
    products compute, whole row tiles (`ragged_tile_rows`); rows that
    live tokens routed to held experts; and the largest count of those
    on one held expert).

    The assignments that land on held experts are sorted by expert (a
    stable sort: ties keep token order) and run through grouped matrix
    products whose groups are the experts' rows; the results are put
    back in (token, slot) order and summed over the token's slots with
    its gates, so each row's output is its own experts' outputs alone.
    The shared experts run for every row."""
    shape = x.shape
    d, k, e = shape[-1], cfg.top_k, cfg.n_held
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    with jax.named_scope("moe_route"):
        _, _, gates, experts = moe_route(p, xf, cfg)
        hit = _held(cfg, experts) & live.reshape(n, 1)        # (N, k)
        key = jnp.where(hit, experts, e).reshape(-1)          # off-share: e
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
        rows = xf[order // k]                                 # (N*k, D)
    with jax.named_scope("moe_experts"):
        up = jax.lax.ragged_dot(rows, p["w_up"], sizes)
        if act == "swiglu":
            h = jax.nn.silu(jax.lax.ragged_dot(rows, p["w_gate"], sizes)) \
                * up
        else:
            h = jax.nn.gelu(up)
        ys = jax.lax.ragged_dot(h, p["w_down"], sizes)        # (N*k, D)
        # back to (token, slot) order; slots off the share add nothing
        # (rows past the groups are not defined by ragged_dot)
        ys = jnp.zeros_like(ys).at[order].set(ys).reshape(n, k, d)
        ys = jnp.where(hit[..., None], ys.astype(jnp.float32), 0.0)
        y = (ys * gates[..., None]).sum(1).astype(x.dtype)
    if cfg.num_shared > 0:
        with jax.named_scope("moe_shared"):
            y = y + mlp_forward(p["shared"], xf, act)
    counts = {"expert_rows": _tile_rows(sizes, n * k),
              "expert_rows_live": hit.sum(dtype=jnp.int32),
              "expert_rows_max": sizes.max()}
    return y.reshape(shape), counts


def moe_forward(p: dict, x: jax.Array, cfg: MoEConfig, act: str):
    """Training dispatch.  x: (B, S, D) -> (y, aux_losses dict)."""
    b, s, d = x.shape
    g, ng = _group_shape(b, s)
    e, k = cfg.n_held, cfg.top_k
    cap = max(8, min(int(cfg.capacity_factor * k * ng / e), ng * k))
    xg = x.reshape(g, ng, d)

    logits, probs, gate_vals, assign = moe_route(p, xg, cfg)  # (G, Ng, *)
    held = _held(cfg, assign)

    # ---- grouped sort-based dispatch -----------------------------------
    # experts this chip does not hold sort last (id e) and are dropped
    flat_e = jnp.where(held, assign, e).reshape(g, ng * k)
    order = jnp.argsort(flat_e, axis=-1, stable=True)        # (G, Ng*k)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    # position within expert = rank - start-of-expert (per group)
    starts = jax.vmap(lambda se: jnp.searchsorted(se, jnp.arange(e + 1)))(
        sorted_e)                                            # (G, E + 1)
    rank = jnp.arange(ng * k, dtype=jnp.int32)[None, :]
    pos = rank - jnp.take_along_axis(starts, sorted_e, axis=-1).astype(
        jnp.int32)
    keep = (pos < cap) & (sorted_e < e)
    tok = (order // k).astype(jnp.int32)                     # source token
    slot_gate = jnp.take_along_axis(gate_vals.reshape(g, ng * k), order,
                                    axis=-1)

    gidx = jnp.broadcast_to(jnp.arange(g)[:, None], (g, ng * k))
    se = jnp.where(keep, sorted_e, e)                        # drop -> OOB
    ps = jnp.where(keep, pos, 0)
    buf_tok = jnp.full((g, e, cap), ng, jnp.int32)           # pad row = ng
    buf_tok = buf_tok.at[gidx, se, ps].set(tok, mode="drop")
    buf_gate = jnp.zeros((g, e, cap), x.dtype)
    buf_gate = buf_gate.at[gidx, se, ps].set(slot_gate.astype(x.dtype),
                                             mode="drop")

    x_pad = jnp.concatenate([xg, jnp.zeros((g, 1, d), x.dtype)], axis=1)
    xe = jnp.take_along_axis(x_pad[:, :, None, :],
                             buf_tok.reshape(g, -1, 1, 1), axis=1
                             )[:, :, 0, :].reshape(g, e, cap, d)

    # ---- expert-parallel batched matmuls --------------------------------
    # anchor the group dim on the batch mesh axes (other dims replicated;
    # GSPMD otherwise re-gathers G across data inside the expert einsums —
    # explicitly co-sharding the expert dim was tried and REFUTED: Shardy
    # lands on a worse fixed point, wire 5x)
    # (EXPERIMENTS.md §Perf, phi3.5-moe prefill)
    xe = constrain_batch(xe, batch_dim=0)
    up = jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])) * up
    else:
        h = jax.nn.gelu(up)
    h = constrain_batch(h, batch_dim=0)
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])        # (G, E, C, D)
    ye = constrain_batch(ye, batch_dim=0)

    # ---- combine: weighted scatter-add ----------------------------------
    # anchor the scatter OPERAND's group dim — otherwise the expert-partial
    # all-reduce runs on the full unsharded (G, Ng, D) tensor
    ye = ye * buf_gate[..., None]
    y = constrain_batch(jnp.zeros((g, ng + 1, d), x.dtype), batch_dim=0)
    gidx2 = jnp.broadcast_to(jnp.arange(g)[:, None], (g, e * cap))
    y = y.at[gidx2, buf_tok.reshape(g, e * cap)].add(
        ye.reshape(g, e * cap, d), mode="drop")
    y = constrain_batch(y, batch_dim=0)
    y = y[:, :ng].reshape(b, s, d)

    if cfg.num_shared > 0:
        y = y + mlp_forward(p["shared"], x, act)

    # ---- aux losses (GShard load balance + router z) --------------------
    n_e = cfg.num_experts
    me = probs.mean(axis=(0, 1))                             # (E,)
    one_hot = jax.nn.one_hot(assign, n_e, dtype=jnp.float32)
    ce = one_hot.sum(axis=(0, 1, 2)) / (g * ng * k)
    lb = n_e * jnp.sum(me * ce)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = {"moe_load_balance": cfg.router_aux_weight * lb,
           "moe_router_z": cfg.router_z_weight * z}
    return y, aux
