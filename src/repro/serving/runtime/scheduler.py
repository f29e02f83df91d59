"""Lane scheduling: fixed-width slots, immediate recycling, static
shapes (DESIGN.md §7; paged KV §8; chunked prefill §9).

Three layers:

  * `LaneScheduler` — the pure allocator.  `n_lanes` slots; a lane is
    recycled the moment its request finishes (or its stream hits EOS);
    admission pops the `RequestQueue` into free lanes, optionally gated
    by a ``can_admit`` callback (the paged-KV stepper's page-budget
    reservation: when the pool can't cover a request's worst case, the
    request STAYS QUEUED — head-of-line, deterministic — instead of
    being dropped).  All bookkeeping is host-side numpy, so the device
    batch keeps one static shape and occupancy is just a mask.

  * `EngineStepper` — the device-state surgery for the REAL model.  It
    owns the batched decode caches / current tokens / positions / the
    carried strategy-bank states, admits one request by prefilling it
    at batch 1 and pytree-scattering the results into the lane slot, and
    steps all lanes through the shared `serving.engine.make_token_step`
    program (carry_state mode).  ``kv="paged"`` swaps the per-lane ring
    caches for the `serving.kvpool` page pool: admission scatters the
    prefill KV into allocated pages (shared-prefix tokens skip straight
    to the sink — their pages already hold the bytes), each token step
    first executes the pool's host-planned page ops (fresh-page position
    resets, copy-on-write splits) and then decodes against per-lane page
    tables.  A recycled lane's strategy state is sliced back to
    fresh-init at admission via `strategy.init_lane`; per-token
    strategies are additionally re-sliced at every token boundary inside
    the step, while ``persistent = True`` strategies carry state across
    a request's tokens and rely on the admission reset alone — either
    way, state from a previous occupant can never leak into the next
    request.  ``prefill_chunk=N`` replaces the batch-1 admission
    prefill with CHUNKED prefill co-scheduled with decode (§9): admit
    only allocates pages and registers a cursor; each `step` then runs
    decode AND a planner-budgeted prefill chunk in one fused program.

  * `ChunkPlanner` — the per-step token budget for those chunks, split
    fairly across prompt-length buckets (long prompts can't starve
    short ones); shared with the sim stepper so sweeps exercise the
    served discipline.

Per-lane masked cache writes inside the token step make each lane's
output stream a function of its own request only, so the scheduler's
admission order cannot change what any request generates
(tests/serving/test_runtime.py pins this).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as A
from repro.models import model as M
from repro.models.attention import PagedKV, PrefillChunk
from repro.serving.engine import make_token_step
from repro.serving.kvpool.alloc import GARBAGE_PAGE
from repro.serving.obs.trace import TRACER, StepRecord
from repro.serving.runtime.request import Request, RequestQueue
from repro.strategy.base import init_lane

__all__ = ["LaneScheduler", "ChunkPlanner", "EngineStepper"]


class LaneScheduler:
    """Fixed-width lane allocator with immediate recycling."""

    def __init__(self, n_lanes: int):
        if n_lanes < 1:
            raise ValueError("need at least one lane")
        self.n_lanes = int(n_lanes)
        self.lane_req: list[Request | None] = [None] * self.n_lanes
        self.remaining = np.zeros(self.n_lanes, np.int64)
        self.sid = np.zeros(self.n_lanes, np.int32)

    def occupied_mask(self) -> np.ndarray:
        return np.asarray([r is not None for r in self.lane_req])

    def busy(self) -> bool:
        return any(r is not None for r in self.lane_req)

    def free_lanes(self) -> list[int]:
        return [i for i, r in enumerate(self.lane_req) if r is None]

    def admit(self, queue: RequestQueue, sid_of, *,
              static_batching: bool = False,
              can_admit=None) -> list[tuple[int, Request]]:
        """Pop queued requests into free lanes; returns assignments.

        ``static_batching=True`` reproduces the fixed-batch
        `Engine.generate` discipline (the bench baseline): a new batch
        is admitted only once EVERY lane is free, so stragglers idle the
        whole width.

        ``can_admit(req)`` gates (and RESERVES resources for) each pop —
        the paged-KV page budget.  A False verdict stops admission at
        the queue head: the request waits, later arrivals wait behind it
        (deterministic head-of-line order; no starvation, no drops).
        """
        if static_batching and self.busy():
            return []
        out = []
        for lane in self.free_lanes():
            if not len(queue):
                break
            if can_admit is not None and not can_admit(queue.peek()):
                break
            req = queue.pop()
            self.lane_req[lane] = req
            self.remaining[lane] = req.max_tokens
            self.sid[lane] = sid_of(req)
            out.append((lane, req))
        return out

    def consume_token(self, lane: int) -> bool:
        """Account one emitted token; True when the budget is exhausted."""
        self.remaining[lane] -= 1
        return bool(self.remaining[lane] <= 0)

    def release(self, lane: int) -> Request:
        req = self.lane_req[lane]
        if req is None:
            raise ValueError(f"lane {lane} is already free")
        self.lane_req[lane] = None
        self.remaining[lane] = 0
        self.sid[lane] = 0
        return req


class ChunkPlanner:
    """Per-step prefill-chunk planning under a token budget with
    prompt-length-bucketed fairness (DESIGN.md §9).

    Each step, at most ``budget`` prompt tokens are spread over the
    lanes currently mid-prefill, every lane capped at ``chunk`` tokens
    (the device chunk width).  Lanes are grouped into power-of-two
    prompt-length BUCKETS (in units of ``chunk``) and the budget is
    split evenly across the nonempty buckets — a lane prefilling a
    4096-token prompt can take at most its bucket's share, so freshly
    admitted short prompts always find budget and reach their first
    token in O(1) steps instead of queueing behind the long prefill
    (and vice versa: the long prompt keeps its share no matter how many
    shorts arrive, so neither side starves).  Within a bucket a
    rotating round-robin pointer decides who goes first; the
    budget-split remainder rotates across buckets.  Unused share flows
    to the next bucket, then tops up any lane still under its cap —
    the budget is never wasted while work remains.

    Used by both the real `EngineStepper` and the virtual-clock
    `SimStepper`, so the sim sweeps exercise the exact admission
    discipline the engine serves with.
    """

    def __init__(self, chunk: int, budget: int | None = None):
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        self.chunk = int(chunk)
        self.budget = int(budget) if budget is not None else self.chunk
        if self.budget < 1:
            raise ValueError("budget must be >= 1")
        self._rr = 0

    def bucket(self, prompt_len: int) -> int:
        """Power-of-two bucket index: 0 for prompts up to one chunk,
        then doubling (chunk, 2*chunk] -> 1, (2c, 4c] -> 2, ..."""
        return max(0, -(-int(prompt_len) // self.chunk) - 1).bit_length()

    def plan(self, lanes: dict) -> dict:
        """``lanes``: lane -> (remaining_tokens, prompt_len).  Returns
        lane -> tokens to prefill this step (each in [1, chunk], total
        <= budget)."""
        if not lanes:
            return {}
        buckets: dict[int, list[int]] = {}
        for lane in sorted(lanes):
            buckets.setdefault(self.bucket(lanes[lane][1]), []).append(lane)
        keys = sorted(buckets)
        base, rem = divmod(self.budget, len(keys))
        rem_at = self._rr % len(keys)

        def rotated(seq):
            off = self._rr % len(seq)
            return seq[off:] + seq[:off]

        out: dict[int, int] = {}
        leftover = 0
        for i, bk in enumerate(keys):
            share = base + (rem if i == rem_at else 0) + leftover
            for lane in rotated(buckets[bk]):
                w = min(self.chunk, lanes[lane][0], share)
                if w > 0:
                    out[lane] = w
                    share -= w
            leftover = share
        if leftover > 0:       # top-up pass: no budget left stranded
            for lane in rotated(sorted(lanes)):
                got = out.get(lane, 0)
                add = min(self.chunk - got, lanes[lane][0] - got, leftover)
                if add > 0:
                    out[lane] = got + add
                    leftover -= add
                if leftover == 0:
                    break
        self._rr += 1
        return out


def _materialize_cache(spec, key=None):
    """Zero-filled decode cache from a `models.model.cache_specs` tree
    (attention ``pos`` buffers start at -1 == empty slot)."""
    if isinstance(spec, dict):
        return {k: _materialize_cache(v, k) for k, v in spec.items()}
    shape, dtype = spec
    if key == "pos":
        return jnp.full(shape, -1, dtype)
    return jnp.zeros(shape, dtype)


class EngineStepper:
    """Real-model lane state: batched caches + the shared token step."""

    virtual_time = False
    emits_tokens = True    # `emitted` really is token ids (EOS applies)
    # observability plane (DESIGN.md §12): installed by the server when
    # tracing is on; every producer guards on `is not None`
    tracer = None
    # step- and request-granular spans, always on: the server installs
    # the tracer of its serve
    spans = TRACER

    def __init__(self, params, cfg, strategies: tuple, *, n_lanes: int,
                 cache_len: int, prompt_len: int, jit: bool = True,
                 kv: str = "ring", page_size: int = 16,
                 n_pages: int | None = None, paged_kernel: bool = False,
                 prefill_chunk: int | None = None,
                 prefill_budget: int | None = None,
                 node_offset: int = 0, walk_io: bool = False,
                 resume_walk: bool = False,
                 max_lane_pages: int | None = None,
                 model_key: str | None = None):
        if kv not in ("ring", "paged"):
            raise ValueError(f"unknown kv mode {kv!r} (ring|paged)")
        prefill_chunk = prefill_chunk or None      # 0 == disabled
        if prefill_chunk is not None:
            if kv != "paged":
                raise ValueError("chunked prefill needs --kv paged "
                                 "(chunks commit into the page pool)")
            for seg in cfg.segments:
                if seg.block.mixer != "attn":
                    raise ValueError(
                        "chunked prefill supports attention segments "
                        "only (GQA and MLA; SSM state is sequential "
                        "over the prompt) — drop --prefill-chunk for "
                        f"mixer {seg.block.mixer!r}")
        self.params = params
        self.cfg = cfg
        self.strategies = strategies
        self.n_lanes = int(n_lanes)
        self.cache_len = int(cache_len)
        self.prompt_len = int(prompt_len)
        self.full_depth = len(cfg.segments)
        self.kv = kv
        self.prefill_chunk = None if prefill_chunk is None \
            else int(prefill_chunk)
        self.planner = None if prefill_chunk is None else ChunkPlanner(
            self.prefill_chunk, prefill_budget)
        self.walk_io = bool(walk_io)
        self._n_up = self._up_bytes = 0     # host->device arrays made
        # every program that writes the caches takes them donated off the
        # CPU (make_token_step's rule), so the pool is updated in place
        # and never held twice
        donate = {"donate_argnums": (0,)} \
            if jax.default_backend() != "cpu" else {}
        self._step = make_token_step(params, cfg, strategies, jit=jit,
                                     carry_state=True,
                                     paged=(kv == "paged"),
                                     paged_kernel=paged_kernel,
                                     prefill_slots=self.prefill_chunk or 0,
                                     node_offset=node_offset,
                                     walk_io=walk_io,
                                     resume_walk=resume_walk)
        # the step's expert counters (a trailing output for models with
        # expert layers) and the latent-attention layers per segment,
        # whose reads the step record counts on the host
        self._counted = any(seg.block.mlp == "moe" for seg in cfg.segments)
        self._mla_layers = [
            seg.n_layers if seg.block.attn is not None
            and seg.block.attn.mla is not None else 0
            for seg in cfg.segments]
        if kv == "paged":
            from repro.serving.kvpool import KVPool
            lane_pages = -(-self.cache_len // page_size)
            self.pool = KVPool(n_lanes=self.n_lanes, page_size=page_size,
                               lane_pages=lane_pages, n_pages=n_pages,
                               max_lane_pages=max_lane_pages,
                               model_key=model_key)
            admit_fn = self._make_paged_admit()
            self._prep = jax.jit(self._paged_prep, **donate) if jit \
                else self._paged_prep
            self._reset = jax.jit(self._reset_pages, **donate) if jit \
                else self._reset_pages
        else:
            self.pool = None

            def admit_fn(params, caches, tok, pos, prompt, lane):
                logits, pc, _, npos = M.prefill(params, cfg,
                                                {"tokens": prompt},
                                                cache_len)
                t0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]

                def scatter(full, one):
                    return full.at[:, lane].set(one[:, 0].astype(full.dtype))

                caches = jax.tree.map(scatter, caches, pc)
                return (caches, tok.at[lane].set(t0),
                        pos.at[lane].set(npos[0].astype(jnp.int32)))

        # weights enter the admit program as an argument, never as
        # constants (see make_token_step); the caches follow them
        self._admit = functools.partial(
            jax.jit(admit_fn, donate_argnums=tuple(
                i + 1 for i in donate.get("donate_argnums", ())))
            if jit else admit_fn, params)
        self.alloc()

    # ---- paged device programs ----------------------------------------

    def _make_paged_admit(self):
        cfg, prompt_len = self.cfg, self.prompt_len

        def admit_fn(params, caches, tok, pos, prompt, lane, dest_page,
                     pos_vals, new_pages):
            # prefill at cache_len == prompt_len: the ring layout is the
            # identity (slot t <- position t), so the per-token page
            # scatter below reads positions straight through
            logits, pc, _, npos = M.prefill(params, cfg,
                                            {"tokens": prompt}, prompt_len)
            t0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
            # the prompt's rows (position t in slot t % page of its page)
            # land in place; shared-prefix tokens (garbage destination)
            # write nothing
            ps = self.pool.page_size
            targets = A.pool_targets(
                dest_page[None], jnp.zeros((1,), jnp.int32),
                dest_page[None] != GARBAGE_PAGE, -(-prompt_len // ps), ps)
            out = []
            for si in range(len(cfg.segments)):
                seg_c = dict(caches[si])
                if "attn" in seg_c:
                    attn = dict(seg_c["attn"])
                    # gate stale bytes of freshly allocated pages
                    # (garbage-page padding makes this idempotent)
                    attn["pos"] = attn["pos"].at[:, new_pages].set(-1)
                    n_layers = attn["pos"].shape[0]
                    rows = dict(pc[si]["attn"], pos=jnp.broadcast_to(
                        pos_vals, (n_layers, 1, prompt_len)))
                    for name in attn:
                        for li in range(n_layers):
                            attn[name] = A.pool_write(
                                attn[name], li, targets, rows[name][li])
                    seg_c["attn"] = attn
                if "ssm" in seg_c:
                    seg_c["ssm"] = jax.tree.map(
                        lambda full, one: full.at[:, lane].set(
                            one[:, 0].astype(full.dtype)),
                        seg_c["ssm"], pc[si]["ssm"])
                out.append(seg_c)
            return (out, tok.at[lane].set(t0),
                    pos.at[lane].set(npos[0].astype(jnp.int32)))

        return admit_fn

    @staticmethod
    @jax.named_scope("page_reset")
    def _reset_pages(caches, pages):
        """Gate the stale bytes of freshly allocated pages before a
        chunked admission starts writing into them: pos[:, pages] = -1
        across every attention layer.  ``pages`` is garbage-padded
        (the sink's positions are -1 by construction, so re-resetting
        it is a no-op)."""
        out = []
        for seg_c in caches:
            seg_c = dict(seg_c)
            if "attn" in seg_c:
                attn = dict(seg_c["attn"])
                attn["pos"] = attn["pos"].at[:, pages].set(-1)
                seg_c["attn"] = attn
            out.append(seg_c)
        return out

    @staticmethod
    @jax.named_scope("page_prep")
    def _paged_prep(caches, fresh, cow_src, cow_dst):
        """Pre-step page ops: COW page copies (src -> dst across every
        attention layer — page ids are global) and fresh-page position
        resets.  Idle entries are garbage-page pairs (0 -> 0), which
        copy the sink onto itself."""
        out = []
        for seg_c in caches:
            seg_c = dict(seg_c)
            if "attn" in seg_c:
                attn = {name: leaf.at[:, cow_dst].set(leaf[:, cow_src])
                        for name, leaf in seg_c["attn"].items()}
                attn["pos"] = attn["pos"].at[:, fresh].set(-1)
                seg_c["attn"] = attn
            out.append(seg_c)
        return out

    # ---- lane state ----------------------------------------------------

    def alloc(self) -> None:
        """(Re)build empty lane state: zero caches, fresh bank states."""
        if self.pool is not None:
            self.pool.reset()
            specs = M.paged_cache_specs(self.cfg, self.n_lanes,
                                        self.pool.n_pages,
                                        self.pool.page_size)
        else:
            specs = M.cache_specs(self.cfg, self.n_lanes, self.cache_len)
        self.caches = None      # free the old caches before the new exist
        self.caches = [_materialize_cache(s) for s in specs]
        self.tok = jnp.zeros((self.n_lanes,), jnp.int32)
        self.pos = jnp.zeros((self.n_lanes,), jnp.int32)
        # host views for the per-step record: each lane's request and
        # the position its next decode token is written at
        self.lane_rids = np.full(self.n_lanes, -1, np.int64)
        self.host_pos = np.zeros(self.n_lanes, np.int64)
        self.states = tuple(s.init(self.n_lanes) for s in self.strategies)
        # chunked-prefill lane state: lane -> {prompt, plan, cursor, lp}
        self._prefilling = {}
        self._idle_chunk = None
        self.chunk_stats = {"tokens_computed": 0, "tokens_skipped": 0,
                            "chunk_steps": 0, "prefills": 0}

    def reserve(self, req: Request) -> bool:
        """Admission gate (the scheduler's ``can_admit``): reserve the
        request's worst-case page need.  Ring mode has nothing to
        reserve — lane availability is the only constraint."""
        if self.pool is None:
            return True
        return self.pool.reserve(req.prompt, req.max_tokens)

    def release(self, lane: int) -> None:
        """Return the lane's pages to the pool (prefix-cache refs keep
        shared prompt pages warm).  Ring lanes have nothing to return.
        A lane reaped mid-chunked-prefill (fault plane) also drops its
        prefill cursor — otherwise the freed lane would keep receiving
        chunk plans."""
        with self.spans.span("pool.release", rid=self.lane_rids[lane],
                             lane=lane):
            self._prefilling.pop(lane, None)
            self.lane_rids[lane] = -1
            if self.pool is not None:
                self.pool.release(lane)

    def admit(self, lane: int, req: Request) -> None:
        """Admit the request into ``lane``.

        Stop-the-world mode: prefill at batch 1 and scatter the result
        into the lane slot (stalls every decode lane for the whole
        prompt).  Chunked mode (``prefill_chunk``): allocate the
        prompt's pages NOW, but defer the compute — the prompt is fed
        through the fused token step ``prefill_chunk`` tokens at a
        time, co-scheduled with decode, and prefix-cache hits skip
        their already-cached chunks entirely.  Chunked admission also
        lifts the fixed prompt bucket: any prompt that fits the lane's
        page capacity is admissible (chunks are the static shape, not
        the prompt)."""
        with self.spans.span("engine.admit", rid=req.rid,
                             lane=lane) as span:
            n_up = self._n_up
            self.lane_rids[lane] = req.rid
            self._admit_lane(lane, req)
            span.add(uploads=self._n_up - n_up)

    def _admit_lane(self, lane: int, req: Request) -> None:
        spans = self.spans
        if self.prefill_chunk is not None:
            with spans.span("pool.admit", rid=req.rid, lane=lane):
                plan = self.pool.admit(lane, req.prompt, req.max_tokens,
                                       register_prefix=False)
            with spans.span("engine.page_ops", op="reset"):
                self.caches = self._reset(self.caches,
                                          self._put(plan.new_pages))
            lp = int(req.prompt.shape[0])
            # full prefix hit still recomputes the final token: the
            # first-token logits need the last position's hidden state
            cursor = min(plan.n_shared_tokens, lp - 1)
            self.chunk_stats["tokens_skipped"] += cursor
            self.chunk_stats["prefills"] += 1
            self._prefilling[lane] = {
                "prompt": np.asarray(req.prompt, np.int32),
                "plan": plan, "cursor": cursor, "lp": lp,
                "rid": req.rid}
            self.states = tuple(
                init_lane(s, st, lane)
                for s, st in zip(self.strategies, self.states))
            return
        if req.prompt.shape[0] != self.prompt_len:
            raise ValueError(
                f"request {req.rid}: prompt length {req.prompt.shape[0]} "
                f"!= stepper bucket {self.prompt_len} (static shapes)")
        prompt = self._put(req.prompt, jnp.int32)[None, :]
        if self.pool is not None:
            with spans.span("pool.admit", rid=req.rid, lane=lane):
                plan = self.pool.admit(lane, req.prompt, req.max_tokens)
            self.caches, self.tok, self.pos = self._admit(
                self.caches, self.tok, self.pos, prompt,
                self._put(lane, jnp.int32), self._put(plan.dest_page),
                self._put(plan.pos_vals), self._put(plan.new_pages))
        else:
            self.caches, self.tok, self.pos = self._admit(
                self.caches, self.tok, self.pos, prompt,
                self._put(lane, jnp.int32))
        self.host_pos[lane] = self.prompt_len
        # pytree-sliced per-lane reset: the recycled lane starts from
        # fresh strategy state no matter what its predecessor observed
        self.states = tuple(init_lane(s, st, lane)
                            for s, st in zip(self.strategies, self.states))

    def _latent_reads(self, seg_batch: int, pos_h, rows) -> dict:
        """The latent rows the step's MLA attention read and the live
        positions among them, from the gather widths: decode gathers
        every lane's whole page table (T rows) in each MLA layer of the
        segments it launched, the chunk sweep the page table of each
        prefilling lane in every MLA layer.  Live are the positions the
        attending lanes hold: each decoding lane's context (its new
        token included) and each chunk's end (holes of early-exited
        tokens counted as live)."""
        width = (self.pool.lane_pages * self.pool.page_size
                 if self.pool is not None else self.cache_len)
        dec = sum(self._mla_layers[:seg_batch])
        full = sum(self._mla_layers)
        ctx = int(np.sum(pos_h + 1))
        ends = sum(int(r[2] + r[3]) for r in rows)
        return {"latent_rows_read": (self.n_lanes * dec + len(rows) * full)
                * width,
                "latent_rows_live": ctx * dec + ends * full}

    def _put(self, x, dtype=None) -> jax.Array:
        """One host->device array, counted for the step's span."""
        out = jnp.asarray(x, dtype)
        self._n_up += 1
        self._up_bytes += out.nbytes
        return out

    def set_lane_token(self, lane: int, token: int) -> None:
        """Override a lane's next input token — the cascade router uses
        this after an escalation catch-up prefill: the finishing chunk
        seeds its own head argmax, but the escalated stream's next input
        is the token the SOURCE model already emitted."""
        self.tok = self.tok.at[lane].set(jnp.int32(token))

    def warmup(self) -> None:
        """Compile the admit + prep + step programs off the serving
        clock."""
        dummy = Request(rid=-1, prompt=np.zeros(self.prompt_len, np.int32),
                        max_tokens=1)
        if not self.reserve(dummy):
            from repro.serving.kvpool import PoolExhausted
            raise PoolExhausted(
                f"kv pool of {self.pool.n_pages} pages x "
                f"{self.pool.page_size} tokens cannot fit even one "
                f"{self.prompt_len}-token request — raise --pages or "
                "--page-size")
        self.admit(0, dummy)
        occ = np.zeros((self.n_lanes,), bool)
        occ[0] = True
        if self.pool is not None:
            # compile the page-ops program too (an all-garbage plan is a
            # no-op: it copies the sink onto itself)
            idle = jnp.zeros((self.n_lanes,), jnp.int32)
            self.caches = self._prep(self.caches, idle, idle, idle)
        sid0 = np.zeros((self.n_lanes,), np.int32)
        # chunked mode: drive the dummy's whole prefill through the
        # fused step (compiles the chunk-active branch), then decode
        # once (compiles the chunk-idle + decode branch)
        for _ in range(2 * self.prompt_len + 2):
            if not self._prefilling:
                break
            self.step(occ, sid0)
        self.step(occ, sid0)
        self.alloc()

    def _build_chunk(self, widths: dict):
        """Turn the planner's lane -> width map into the device
        `PrefillChunk` (all-idle when nothing is prefilling: position
        -1 rows, garbage destinations — the step's lax.cond skips the
        sweep).  Advances the per-lane cursors and returns the lanes
        whose prompt finishes with this chunk, and the chunks' rows of
        the step record (lane, rid, start, width, done)."""
        n, c = self.n_lanes, self.prefill_chunk
        rows = []
        if not widths:
            if self._idle_chunk is None:
                zi = jnp.zeros((n, c), jnp.int32)
                zb = jnp.zeros((n,), bool)
                z1 = jnp.zeros((n,), jnp.int32)
                self._idle_chunk = PrefillChunk(
                    tok=zi, pos=jnp.full((n, c), -1, jnp.int32),
                    dest_page=zi, dest_slot=zi, start=z1, last_idx=z1,
                    emit=zb, active=zb)
            return self._idle_chunk, [], rows
        tok = np.zeros((n, c), np.int32)
        pos = np.full((n, c), -1, np.int32)
        dp = np.zeros((n, c), np.int32)     # 0 == the garbage sink
        ds = np.zeros((n, c), np.int32)
        start = np.zeros(n, np.int32)
        last = np.zeros(n, np.int32)
        emit = np.zeros(n, bool)
        act = np.zeros(n, bool)
        finished = []
        for lane, w in widths.items():
            st = self._prefilling[lane]
            cur = st["cursor"]
            sl = slice(cur, cur + w)
            tok[lane, :w] = st["prompt"][sl]
            pos[lane, :w] = np.arange(cur, cur + w, dtype=np.int32)
            dp[lane, :w] = st["plan"].dest_page[sl]
            ds[lane, :w] = st["plan"].dest_slot[sl]
            start[lane] = cur
            last[lane] = w - 1
            act[lane] = True
            st["cursor"] = cur + w
            done = st["cursor"] == st["lp"]
            if done:
                emit[lane] = True
                finished.append(lane)
            rows.append((lane, st.get("rid", -1), cur, w, done))
            self.chunk_stats["tokens_computed"] += w
            if self.tracer is not None:
                self.tracer.emit(
                    "prefill_chunk", lane=int(lane),
                    rid=int(st.get("rid", -1)), width=int(w),
                    left=int(st["lp"] - st["cursor"]))
        self.chunk_stats["chunk_steps"] += 1
        put = self._put
        chunk = PrefillChunk(
            tok=put(tok), pos=put(pos), dest_page=put(dp),
            dest_slot=put(ds), start=put(start), last_idx=put(last),
            emit=put(emit), active=put(act))
        return chunk, finished, rows

    def step(self, occupied: np.ndarray, sid: np.ndarray, walk=None):
        """One fused step: a decode token for every occupied DECODING
        lane and — in chunked mode — a budgeted prefill chunk for the
        admitting lanes, in one device program.

        Returns host-side ``(emitted (B,), served (B,), seg_batch,
        seg_policy, emit_mask (B,) bool)`` — a single device sync for
        the whole step.  ``emit_mask`` marks the lanes whose ``emitted``
        entry is a real token (lanes mid-prefill emit nothing).

        ``walk_io`` steppers (the cascade's per-model rungs) also take
        an optional ``walk`` handoff pair ``(active (B,) bool,
        best_logits (B, vocab) f32)`` — omitted, every occupied lane
        starts a fresh walk — and return an extra trailing element
        ``(walk_active (B,) bool host, best_logits device)``: the
        escalation handoff the cascade router stashes for the next
        ladder model.

        The step is the ``engine.step`` span, with children
        ``engine.plan`` (holding ``pool.prepare_step`` and
        ``engine.page_ops``), ``engine.dispatch``, ``pool.commit_prefix``
        and ``engine.sync``; its data holds the counts ``uploads``,
        ``upload_bytes``, ``seg_batch``, ``seg_policy`` (and
        ``compiles``), for a model with expert layers the step's
        ``expert_rows``, ``expert_rows_live`` and ``expert_rows_max``
        (`models.moe.moe_serve`, fetched with the step's one sync), for
        a model with latent attention ``latent_rows_read`` and
        ``latent_rows_live`` (`_latent_reads`), and the public per-step
        record (``record``, a `StepRecord`, whose ``counts`` repeat
        these counters).
        """
        with self.spans.span("engine.step") as span:
            return self._fused_step(occupied, sid, walk, span)

    def _fused_step(self, occupied, sid, walk, span):
        spans = self.spans
        n_up, up_bytes = self._n_up, self._up_bytes
        decode = np.asarray(occupied, bool).copy()
        finished: list = []
        rows: list = []
        with spans.span("engine.plan"):
            widths: dict = {}
            if self.prefill_chunk is not None and self._prefilling:
                for lane in self._prefilling:
                    decode[lane] = False
                widths = self.planner.plan({
                    lane: (st["lp"] - st["cursor"], st["lp"])
                    for lane, st in self._prefilling.items()})
            occ = self._put(decode, bool)
            sid_d = self._put(sid, jnp.int32)
            if self.walk_io and walk is None:
                walk = (jnp.ones((self.n_lanes,), bool),
                        jnp.zeros((self.n_lanes, self.cfg.vocab),
                                  jnp.float32))
            if self.pool is not None:
                with spans.span("pool.prepare_step"):
                    plan = self.pool.prepare_step(decode)
                if plan.fresh.any() or plan.cow_dst.any():
                    # page ops only when the plan has any (steady-state
                    # mid-page decode skips the dispatch + pool rewrite);
                    # dispatched before the chunk is built, so the old
                    # pool is freed before the step allocates its output
                    with spans.span("engine.page_ops", op="prep"):
                        self.caches = self._prep(self.caches,
                                                 self._put(plan.fresh),
                                                 self._put(plan.cow_src),
                                                 self._put(plan.cow_dst))
                kv = PagedKV(page_table=self._put(self.pool.table),
                             write_page=self._put(plan.write_page),
                             write_slot=self._put(plan.write_slot))
                args = (self.tok, self.caches, self.pos, occ, sid_d, kv,
                        self.states)
                if self.prefill_chunk is not None:
                    chunk, finished, rows = self._build_chunk(widths)
                    args = args + (chunk,)
                elif self.walk_io:
                    args = args + (None,)
            else:
                args = (self.tok, self.caches, self.pos, occ, sid_d, None,
                        self.states)
                if self.walk_io:
                    args = args + (None,)
            if self.walk_io:
                args = args + (walk,)
        with spans.span("engine.dispatch"):
            out = self._step(*args)
        if self.pool is not None:
            self.pool.note_written(decode)
        counts = {}
        if self._counted:
            out, counts = out[:-1], out[-1]
        if self.walk_io:
            tok, self.caches, served, sb, sp, self.states, walk_out = out
        else:
            tok, self.caches, served, sb, sp, self.states = out
        self.tok = tok
        self.pos = self.pos + occ.astype(jnp.int32)
        lanes = np.flatnonzero(decode)
        pos_h = self.host_pos[lanes]
        self.host_pos[lanes] += 1
        if finished:
            # the final chunk seeded tok[lane] with the first token
            # (inside the fused step); point the lane past its prompt
            # and make its pages shareable now that every byte exists
            lps = [self._prefilling[ln]["lp"] for ln in finished]
            self.pos = self.pos.at[self._put(finished, jnp.int32)].set(
                self._put(lps, jnp.int32))
            self.host_pos[finished] = lps
            for lane in finished:
                st = self._prefilling.pop(lane)
                with spans.span("pool.commit_prefix", rid=st["rid"],
                                lane=lane):
                    self.pool.commit_prefix(lane, st["prompt"])
        with spans.span("engine.sync"):
            if self.walk_io:
                tok_h, served_h, sb_h, sp_h, counts, wa_h = jax.device_get(
                    (tok, served, sb, sp, counts, walk_out[0]))
            else:
                tok_h, served_h, sb_h, sp_h, counts = jax.device_get(
                    (tok, served, sb, sp, counts))
        fin = np.asarray(finished, np.int64)
        counts = {k: int(v) for k, v in counts.items()}
        if any(self._mla_layers):
            counts.update(self._latent_reads(int(sb_h), pos_h, rows))
        span.add(uploads=self._n_up - n_up,
                 upload_bytes=self._up_bytes - up_bytes,
                 seg_batch=int(sb_h), seg_policy=int(sp_h), **counts,
                 record=StepRecord.of(
                     (lanes, self.lane_rids[lanes], pos_h, served_h[lanes],
                      tok_h[lanes]), rows,
                     (fin, self.lane_rids[fin], tok_h[fin]), counts))
        if self.walk_io:
            return (tok_h, served_h, int(sb_h), int(sp_h), decode,
                    (wa_h, walk_out[1]))
        return tok_h, served_h, int(sb_h), int(sp_h), decode
