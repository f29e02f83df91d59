"""Segment-wise serving engine with T-Tamer early exit (the paper's
technique as a first-class serving feature — DESIGN.md §2-3).

The engine executes a decode step SEGMENT BY SEGMENT.  After every ramp
segment it:
  1. computes the loss proxy ell = 1 - confidence for each lane,
  2. hands it to the pluggable `Strategy` (``observe`` updates per-lane
     state and returns the mask of lanes continuing deeper), and
  3. serves, per lane, the logits of whatever node ``strategy.serve``
     designates — argmin ramp under recall, last probed without.

The engine holds NO policy logic of its own: any strategy from
``repro.strategy.make`` (recall index, thresholds, patience, skip
tables, ...) plugs in unchanged, and the same object reproduces its
offline ``strategy.evaluate`` decisions here (tested in
tests/test_system.py).  Strategies with ``online = False`` (the
hindsight oracles) are rejected — segments cannot be un-run.

TPU adaptation (DESIGN.md §3): lanes are fixed-shape; exited lanes are
masked, and the whole token is ONE device program (`make_token_step`):
each segment launch is gated by ``lax.cond(active.any(), ...)``, so the
decision to stop running deeper segments once every lane has exited
("batch-level" saving) is made on device — no host round-trip per
segment.  Segment counters accumulate as device scalars and the host
syncs exactly once per token (tokens + served nodes + stats in a single
``device_get``).  Per-lane policy FLOPs (what a lane-granular runtime
such as per-request dispatch would pay) are accounted separately — both
numbers are reported by the serving benchmarks.

State skew: when a lane exits early, deeper segments' KV/SSM cache
writes are MASKED for that lane (``_mask_lane_writes``) — the holes are
hidden from later attention by the stored-position mask.  This is the
standard early-exit cache policy (cf. Apparate / DeeBERT serving), a
quality-for-latency approximation the T-Tamer cost model already prices
in via the calibration traces; it also makes every lane's output stream
a function of its own request alone, which is what lets the
continuous-batching runtime (repro.serving.runtime) recycle lanes with
admission-order invariance.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models.config import ModelConfig
from repro.strategy.base import Strategy

__all__ = ["Engine", "GenerationStats", "Classifier", "make_token_step",
           "bank_observe", "bank_serve", "fold_readout"]


def _check_online(strategy: Strategy) -> Strategy:
    if not getattr(strategy, "online", True):
        raise ValueError(
            f"{type(strategy).__name__} needs hindsight (online=False) and "
            "cannot drive the serving engine; use strategy.evaluate on "
            "offline traces instead")
    # the engine's aux channel carries predicted labels, NOT support bins
    # — a table strategy built without a Support would silently consume
    # them as bins, so refuse it here rather than serve garbage
    if hasattr(strategy, "support") and strategy.support is None:
        raise ValueError(
            f"{type(strategy).__name__} was built without a Support and "
            "reads bins from the aux channel; the engine supplies "
            "predictions there — construct it with the cascade's Support")
    return strategy


@dataclasses.dataclass
class GenerationStats:
    tokens: np.ndarray              # (B, T) generated tokens
    served_nodes: np.ndarray        # (B, T) which node served each token
    segments_run_batch: int         # segments actually launched (batch)
    segments_run_policy: int        # sum over lanes of nodes probed
    segments_full: int              # full-depth reference


def _mask_lane_writes(new_cache, old_cache, active: jax.Array,
                      paged: bool = False):
    """Keep inactive lanes' cache bits: leaves are layer-stacked
    ``(L, B, ...)``, so broadcast the lane mask over axis 1.

    In paged mode the attention leaves are page-pool shaped (no lane
    axis) and the decode path already wrote nothing for masked lanes —
    only the lane-indexed SSM state still needs the where()."""
    def sel(n, o):
        return jnp.where(active.reshape((1, -1) + (1,) * (n.ndim - 2)),
                         n, o)
    if not paged:
        return jax.tree.map(sel, new_cache, old_cache)
    out = dict(new_cache)
    if "ssm" in new_cache:
        out["ssm"] = jax.tree.map(sel, new_cache["ssm"], old_cache["ssm"])
    return out


def bank_observe(strategies, states, node, losses, preds, active, sid):
    """Fold one node into every bank member's state; lanes only follow
    their own member's continue/stop verdict (``sid`` selects).  Shared
    by the engine's token step and the runtime's simulation stepper."""
    new_states, conts = [], []
    for k, strat in enumerate(strategies):
        mask = active if len(strategies) == 1 else active & (sid == k)
        st, cont = strat.observe(states[k], node, losses, mask, aux=preds)
        new_states.append(st)
        conts.append(cont)
    if len(strategies) == 1:
        return tuple(new_states), conts[0]
    out = jnp.zeros_like(active)
    for k, cont in enumerate(conts):
        out = jnp.where(sid == k, cont, out)
    return tuple(new_states), out


def bank_serve(strategies, states, sid):
    served = strategies[0].serve(states[0]).astype(jnp.int32)
    for k in range(1, len(strategies)):
        served = jnp.where(sid == k,
                           strategies[k].serve(states[k]).astype(jnp.int32),
                           served)
    return served


def fold_readout(strategies, states, node, logits, ell, active, sid, best):
    """Fold one ramp/head readout into the bank: observe the loss proxy,
    then refresh ``best`` with this node's logits for exactly the lanes
    whose SERVED node is this one (post-observe serve() mask — an
    earlier-exited lane's logits are never overwritten by deeper ramps
    or the head).  Shared by the engine's token step and
    `Classifier.classify` so the serve semantics cannot drift apart.

    Returns (states, active, best)."""
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    states, active = bank_observe(strategies, states, node, ell, preds,
                                  active, sid)
    take = bank_serve(strategies, states, sid) == node
    best = jnp.where(take[:, None], logits.astype(jnp.float32), best)
    return states, active, best


def make_token_step(params, cfg: ModelConfig, strategies, *,
                    jit: bool = True, donate: bool | None = None,
                    carry_state: bool = False, paged: bool = False,
                    paged_kernel: bool = False, prefill_slots: int = 0,
                    node_offset: int = 0, walk_io: bool = False,
                    resume_walk: bool = False):
    """Build the one-token segment sweep shared by `Engine.generate` and
    the continuous-batching runtime (`repro.serving.runtime`).

    The whole sweep is a single device program: each segment launch is
    gated by ``lax.cond(active.any(), ...)`` so batch-level skipping is
    decided on device (no per-segment host round-trip), exited lanes'
    cache writes are masked (a lane's stream depends on its own request
    only), and the segment counters accumulate as device scalars so
    callers sync at most once per token.

    Args:
      strategies: a tuple *bank* of online strategies; the per-lane
        ``sid`` (B,) int32 argument picks each lane's member — this is
        how the runtime serves per-request strategies / lambdas inside
        one static-shape batch.  The Engine passes a one-member bank.
      jit: wrap in ``jax.jit`` (caches donated off-CPU).
      donate: override cache-buffer donation (default: on for
        accelerator backends, off on CPU where XLA can't honor it).
      carry_state: runtime mode — the step takes the bank's per-lane
        states as a sixth argument and returns them updated.  By default
        a strategy explores per token, so every occupied lane's state is
        re-initialized at its token boundary via
        `strategy.base.reset_lanes` (pytree-sliced, on device).  A
        strategy that sets ``persistent = True`` opts out of the
        boundary reset: its state survives across the tokens of one
        request and is reset ONLY by the scheduler's admission-time
        `init_lane` — which is also what guarantees, for both kinds, a
        recycled lane can never observe its predecessor's state.

      paged: the caches are the paged KV pool (models.model
        `paged_cache_specs` layout) and the step takes a
        `models.attention.PagedKV` handle after ``sid`` — the per-lane
        page tables plus this token's (page, slot) write targets, both
        planned host-side by `serving.kvpool.KVPool.prepare_step`.
        Exited/unoccupied lanes write nothing into the pool inside the
        decode (same visibility semantics as the ring path's masked
        writes), and the pool is written in place (DESIGN.md §8).
      paged_kernel: trace the paged decode against the Pallas
        paged-attention kernel instead of the jnp page-table gather.
        The `attention.paged_kernel` contextvar is read at TRACE time,
        so this must be decided when the step is built — flipping the
        context manager around calls of an already-compiled step is a
        silent no-op.  Off by default: on CPU the kernel runs in
        interpret mode (correctness only); on TPU it is the hot path.
      node_offset: global id of this model's FIRST node — the multi-
        model cascade runtime (serving.cascade) builds one step per
        ladder model over ONE combined strategy bank, so each model's
        ramps/head must fold under their global node ids (model m's
        nodes are [offset, offset + n_m)).  The default 0 is the
        single-model case.
      walk_io: the step additionally takes a ``walk`` pair ``(active
        (B,) bool, best_logits (B, vocab) f32)`` as its LAST argument
        and returns the updated pair appended to its outputs — the
        ESCALATION HANDOFF BUFFER.  A lane still active after this
        model's head wants to probe a deeper ladder model; its walk
        state + served-so-far logits hand off to that model's step
        (possibly several steps later, after a catch-up prefill) so the
        cross-model walk serves exactly what a single fused program
        would have.
      resume_walk: (needs carry_state + walk_io) this step CONTINUES
        mid-token walks started on an earlier ladder model: the bank
        states arrive pre-folded and are NOT re-initialized at the
        token boundary (the first model's step already did that reset).
        Strategies with ``persistent = True`` are rejected — their
        cross-token state cannot also encode a mid-token handoff.
      prefill_slots: > 0 (paged mode only) grows the step with CHUNKED
        PREFILL co-scheduled with decode (DESIGN.md §9): the step takes
        a `models.attention.PrefillChunk` of up to ``prefill_slots``
        prompt tokens per admitting lane after ``states`` and, inside
        the SAME device program, runs the full-depth chunk sweep
        against the paged pool — no separate batch-1 prefill program,
        no extra host sync, decode lanes keep decoding.  Lanes whose
        chunk finishes the prompt (``chunk.emit``) get their first
        token (argmax of the final-position head logits) returned in
        ``next_tok`` — exactly what the stop-the-world admission would
        have seeded the lane with.

    Returns ``step(tok (B,) i32, caches, pos (B,) i32, occupied (B,)
    bool, sid (B,) i32[, kv][, states][, chunk]) -> (next_tok,
    new_caches, served_node, seg_batch, seg_policy[, states][, walk]
    [, counts])`` — seg_* are int32 scalars counting this token's
    launched segments and per-lane probed segments.  A model with
    expert layers also returns ``counts``, the step's expert-layer
    counters (`models.moe.EXPERT_COUNTS`, int32 scalars summed over the
    layers that ran, decode and chunk sweep); a model without has no
    such output.  It is a `functools.partial` binding
    ``params`` as the first argument of the (jitted) program in
    ``.func``, so the weights enter it as arguments; ``params`` may be
    `jax.eval_shape` structs to lower ``.func`` without materializing
    them.
    """
    import contextlib

    from repro.models.attention import paged_kernel as _paged_kernel_ctx
    from repro.models.moe import EXPERT_COUNTS
    from repro.strategy.base import reset_lanes
    strategies = tuple(_check_online(s) for s in strategies)
    counted = any(seg.block.mlp == "moe" for seg in cfg.segments)
    kernel_ctx = (_paged_kernel_ctx if (paged and paged_kernel)
                  else contextlib.nullcontext)
    if prefill_slots and not paged:
        raise ValueError("prefill_slots needs the paged KV pool "
                         "(chunks are committed page by page)")
    if resume_walk:
        if not (carry_state and walk_io):
            raise ValueError("resume_walk continues a handed-off walk; "
                             "it needs carry_state and walk_io")
        for s in strategies:
            if getattr(s, "persistent", False):
                raise ValueError(
                    f"{type(s).__name__} is persistent — its cross-token "
                    "state cannot double as a mid-token walk handoff")

    def step(params, tok, caches, pos, occupied, sid, kv=None,
             states_in=None, chunk=None, walk=None):
        b = tok.shape[0]
        x = params["embed"]["table"][tok][:, None, :]
        if resume_walk:
            # mid-token continuation: the earlier ladder model's step
            # already reset + folded these states for this token
            states = states_in
        elif carry_state:
            # per-token exploration: every occupied lane starts this
            # token from a fresh state, sliced per lane so unoccupied
            # lanes' (stale, masked-out) leaves stay bit-stable.
            # `persistent` strategies keep their state across tokens
            # (admission's init_lane is their only reset).
            states = tuple(
                st if getattr(s, "persistent", False)
                else reset_lanes(s, st, occupied)
                for s, st in zip(strategies, states_in))
        else:
            states = tuple(s.init(b) for s in strategies)
        active = occupied
        best_logits = jnp.zeros((b, cfg.vocab), jnp.float32)
        if walk_io:
            # escalation handoff in: resume each lane's walk activity
            # and its best-served-so-far logits from the previous
            # ladder model's step
            walk_active, walk_best = walk
            active = occupied & walk_active
            best_logits = walk_best
        seg_batch = jnp.zeros((), jnp.int32)
        seg_policy = jnp.zeros((), jnp.int32)
        counts = {k: jnp.zeros((), jnp.int32) for k in EXPERT_COUNTS} \
            if counted else {}

        def add(a, b):          # a segment without experts counts none
            return {k: v + b.get(k, 0) for k, v in a.items()}
        new_caches = list(caches)
        node = node_offset
        # context entered at TRACE time: selects which attention impl
        # (jnp gather vs Pallas kernel) gets traced into the program
        with kernel_ctx():
            for si, seg in enumerate(cfg.segments):
                seg_batch = seg_batch + active.any().astype(jnp.int32)
                seg_policy = seg_policy + active.sum(dtype=jnp.int32)

                @jax.named_scope(f"segment{si}")
                def run(ops, si=si, node=node):
                    x, cache, states, act, best, cnt = ops
                    # exited lanes route to no expert, paged or not
                    x2, nc, ro, seg_cnt = M.decode_segment(
                        params, cfg, si, x, cache, pos,
                        paged=kv if paged else None,
                        write_mask=act if paged or counted else None,
                        readout_scope=f"readout{node}")
                    cnt = add(cnt, seg_cnt)
                    nc = _mask_lane_writes(nc, cache, act, paged=paged)
                    if ro is not None:
                        # ramp readout: serve-from-this-node logits for
                        # lanes whose served node is the current one (one
                        # head matmul via models.model.ramp_readout;
                        # recall refreshes happen via serve()'s argmin
                        # bookkeeping)
                        with jax.named_scope(f"readout{node}"):
                            states, act, best = fold_readout(
                                strategies, states, node, *ro, act, sid,
                                best)
                    return (x2, nc, states, act, best, cnt)

                ops = (x, caches[si], states, active, best_logits, counts)
                x, new_caches[si], states, active, best_logits, counts = \
                    jax.lax.cond(active.any(), run, lambda o: o, ops)
                if seg.ramp:
                    node += 1

        @jax.named_scope(f"readout{node}")
        def run_head(ops):
            x, states, act, best = ops
            logits, ell = M.ramp_readout(params, cfg, x[:, 0, :])
            states, act, best = fold_readout(strategies, states, node,
                                             logits, ell, act, sid, best)
            return (x, states, act, best)

        ops = (x, states, active, best_logits)
        x, states, active, best_logits = jax.lax.cond(
            active.any(), run_head, lambda o: o, ops)

        next_tok = jnp.argmax(best_logits, axis=-1).astype(jnp.int32)

        if prefill_slots:
            # the co-scheduled prefill chunk: full-depth sweep over the
            # admitting lanes' chunk tokens, traced into the SAME
            # program — the whole step is still one device launch and
            # one host sync.  Decode above never touches these lanes
            # (occupied excludes them), so the only shared state is the
            # page pool, where writes land in disjoint pages.
            with kernel_ctx():
                @jax.named_scope("chunk_sweep")
                def run_chunk(ops):
                    cs, cnt = ops
                    xc = params["embed"]["table"][chunk.tok]
                    cs = list(cs)
                    for si in range(len(cfg.segments)):
                        xc, cs[si], seg_cnt = M.prefill_chunk_segment(
                            params, cfg, si, xc, cs[si], kv.page_table,
                            chunk)
                        cnt = add(cnt, seg_cnt)
                    h = xc[jnp.arange(b), chunk.last_idx, :]
                    logits, _ = M.ramp_readout(params, cfg, h)
                    return ((tuple(cs), cnt),
                            jnp.argmax(logits, axis=-1).astype(jnp.int32))

                def skip_chunk(ops):
                    return ops, jnp.zeros((b,), jnp.int32)

                (chunk_caches, counts), t0 = jax.lax.cond(
                    chunk.active.any(), run_chunk, skip_chunk,
                    (tuple(new_caches), counts))
            new_caches = list(chunk_caches)
            # finishing lanes: seed the lane with its first token, just
            # like the stop-the-world admission would have
            next_tok = jnp.where(chunk.emit, t0, next_tok)

        served = bank_serve(strategies, states, sid)
        out = (next_tok, new_caches, served, seg_batch, seg_policy)
        if carry_state:
            out = out + (states,)
        if walk_io:
            # handoff out: post-head `active` is exactly the escalation
            # signal — the lane's strategy wants to probe a node beyond
            # this model's ladder rung
            out = out + ((active, best_logits),)
        if counted:
            out = out + (counts,)
        return out

    if jit:
        if donate is None:
            donate = jax.default_backend() != "cpu"
        step = jax.jit(step, donate_argnums=(2,) if donate else ())
    # the weights are an argument of the program, not constants baked
    # into it: one compiled step, one copy of the weights on the device
    return functools.partial(step, params)


class Engine:
    """Batched greedy-decode engine with per-token early exit."""

    def __init__(self, params, cfg: ModelConfig, strategy: Strategy,
                 cache_len: int, jit: bool = True):
        self.params = params
        self.cfg = cfg
        self.strategy = _check_online(strategy)
        self.cache_len = cache_len
        self.jit = bool(jit)
        self._step = make_token_step(params, cfg, (self.strategy,),
                                     jit=self.jit)

    def prefill(self, batch: dict):
        return M.prefill(self.params, self.cfg, batch, self.cache_len)

    def generate(self, batch: dict, n_tokens: int) -> GenerationStats:
        cfg = self.cfg
        logits, caches, _, pos = self.prefill(batch)
        b = logits.shape[0]
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        occupied = jnp.ones((b,), bool)
        sid = jnp.zeros((b,), jnp.int32)
        out_tokens, out_nodes = [], []
        seg_batch = seg_policy = 0

        for _ in range(n_tokens):
            tok, caches, served, sb, sp = self._step(tok, caches, pos,
                                                     occupied, sid)[:5]
            # the ONLY host sync of the token: emitted tokens, served
            # nodes, and both segment counters in one transfer
            tok_h, served_h, sb_h, sp_h = jax.device_get(
                (tok, served, sb, sp))
            out_tokens.append(tok_h)
            out_nodes.append(served_h)
            seg_batch += int(sb_h)
            seg_policy += int(sp_h)
            pos = pos + 1

        return GenerationStats(
            tokens=np.stack(out_tokens, 1),
            served_nodes=np.stack(out_nodes, 1),
            segments_run_batch=seg_batch,
            segments_run_policy=seg_policy,
            segments_full=n_tokens * len(cfg.segments) * b,
        )


class Classifier:
    """Classification-mode serving — the paper's §6 experimental setting.

    One request = one input sequence; the prediction is read at the last
    position of a ramp (no decode loop).  The engine runs segment-by-
    segment over the PREFILL, consulting the strategy after each ramp,
    and serves whatever node ``strategy.serve`` designates.  This is
    Alg. 1 applied at the request level, where the latency saving is the
    skipped backbone depth.
    """

    def __init__(self, params, cfg: ModelConfig, strategy: Strategy):
        self.params = params
        self.cfg = cfg
        self.strategy = _check_online(strategy)

    def classify(self, batch: dict) -> dict:
        from repro.models.blocks import block_forward
        cfg = self.cfg
        params = self.params
        strategy = self.strategy
        x, positions = M._embed_inputs(params, cfg, batch)
        b = x.shape[0]
        state = strategy.init(b)
        active = jnp.ones((b,), bool)
        best_logits = jnp.zeros((b, cfg.vocab), jnp.float32)
        node = 0
        seg_run = seg_policy = 0
        n_seg = len(cfg.segments)
        for si, seg in enumerate(cfg.segments):
            if not bool(active.any()):
                break
            p_seg = params["segments"][si]["blocks"]

            def body(h, p_layer, seg=seg):
                y, _, _ = block_forward(p_layer, h, positions, seg.block,
                                        cfg.norm_eps)
                return y, None

            x, _ = jax.lax.scan(body, x, p_seg)
            seg_run += 1
            seg_policy += int(active.sum())
            if seg.ramp:
                # the engine's shared fold: observe, then refresh best
                # logits for lanes whose SERVED node is this ramp
                logits, loss = M.ramp_readout(params, cfg, x[:, -1, :],
                                              segment=si)
                (state,), active, best_logits = fold_readout(
                    (strategy,), (state,), node, logits, loss, active,
                    None, best_logits)
                node += 1
        if bool(active.any()):
            logits, loss = M.ramp_readout(params, cfg, x[:, -1, :])
            (state,), active, best_logits = fold_readout(
                (strategy,), (state,), node, logits, loss, active, None,
                best_logits)
        return {
            "labels": np.asarray(jnp.argmax(best_logits, axis=-1)),
            "served_node": np.asarray(strategy.serve(state)),
            "segments_run_batch": seg_run,
            "segments_run_policy": seg_policy,
            "segments_full": n_seg * b,
        }
