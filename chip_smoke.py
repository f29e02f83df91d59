#!/usr/bin/env python3
"""Bring-up smoke on one TPU chip: serve paper-ee-100m at full width.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py [--seed N]

One process, one chip.  It builds paper-ee-100m at its published widths
(12 layers, d 768, vocabulary 50,257, 6 exit nodes) with seeded random
weights, calibrates a recall-index strategy on the chip, and serves a
seeded open-loop workload through `Server` + `EngineStepper` with the
paged KV pool, chunked prefill and the compiled Pallas kernels.  It then
checks:

  * every request completes with exactly its token budget, the page
    pool's invariants hold, and no page is in use once the prefix cache
    is dropped;
  * `ops.paged_attention` and `ops.paged_prefill`, compiled on the chip,
    agree with their `kernels/ref.py` references (float32, highest
    matmul precision) at the serve's shapes within a bf16 tolerance.

It also serves the same requests through the jnp page-gather path and
prints the share of tokens on which the two agree; random weights give
near-ties, so that share is information, not a check.  Times printed are
from this one run, not a benchmark.  The last line is one JSON object
naming the device.  Without a TPU it exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# deployment-sized serve shape for one v5e chip
LANES = 16
CACHE_LEN = 1024           # tokens per lane
PAGE_SIZE = 128
PROMPT_LEN = 256
MAX_TOKENS = (4, 32)       # per-request decode budget, inclusive
CHUNK = 128                # prefill tokens per lane per step
RATE, DURATION = 64.0, 0.75   # Poisson arrivals/s over this window
LAM = 0.5
KERNEL_TOL = 2e-2          # atol = rtol for bf16 inputs and outputs
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {dev.platform!r})",
              file=sys.stderr)
        sys.exit(1)
    return dev


class _CompileLog:
    """Backend compile (or persistent-cache read) seconds per program."""

    def __init__(self):
        import jax
        self.secs: collections.Counter[str] = collections.Counter()
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, secs, fun_name="?", **_):
        if event == _COMPILE_EVENT:
            self.secs[fun_name] += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self, label: str) -> None:
        total = sum(self.secs.values())
        print(f"compile [{label}]: {total:.2f}s over {len(self.secs)} "
              f"programs, persistent cache {self.hits} hits / "
              f"{self.misses} misses")
        for name, secs in self.secs.most_common(6):
            print(f"  {secs:8.2f}s  {name}")
        self.secs.clear()
        self.hits = self.misses = 0


def _serve(params, cfg, bank, requests, *, paged_kernel: bool, obs=None):
    from repro.serving import runtime as rt
    stepper = rt.EngineStepper(params, cfg, bank, n_lanes=LANES,
                               cache_len=CACHE_LEN, prompt_len=PROMPT_LEN,
                               kv="paged", page_size=PAGE_SIZE,
                               paged_kernel=paged_kernel,
                               prefill_chunk=CHUNK)
    server = rt.Server(stepper, rt.LaneScheduler(LANES), lambda r: 0,
                       slo=1.0, obs=obs)
    metrics = server.serve(requests)
    return metrics, stepper.pool


def _check_serve(metrics, pool, requests, vocab) -> list[str]:
    bad = []
    for req in requests:
        rec = metrics.records.get(req.rid)
        if rec is None or rec.status != "completed":
            bad.append(f"request {req.rid} did not complete")
        elif rec.n_tokens != req.max_tokens:
            bad.append(f"request {req.rid}: {rec.n_tokens} tokens, "
                       f"budget {req.max_tokens}")
        elif not all(0 <= t < vocab for t in rec.tokens):
            bad.append(f"request {req.rid}: token id out of range")
    bad += pool.check_invariants()
    if pool.n_held.any():
        bad.append(f"lanes still hold {int(pool.n_held.sum())} pages")
    pool.prefix.clear()
    if pool.pages_in_use:
        bad.append(f"{pool.pages_in_use} pages in use with no lane "
                   "and no prefix cache")
    bad += pool.check_invariants()
    return bad


def _random_pool(rng, table_lens, maxp, hkv, hd):
    """bf16 k/v pools in the stored layout (DESIGN.md §8) with shuffled
    page ids, and page tables holding positions [0, n) for a lane of
    length n (0 = idle lane)."""
    import jax.numpy as jnp
    n_pages = 1 + LANES * maxp
    shape = (n_pages, hkv, hd, PAGE_SIZE)
    k = jnp.asarray(rng.normal(size=shape) * 0.5, jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=shape) * 0.5, jnp.bfloat16)
    pos = np.full((n_pages, 1, PAGE_SIZE), -1, np.int32)
    table = np.zeros((LANES, maxp), np.int32)
    free = list(rng.permutation(np.arange(1, n_pages)))
    for lane, n in enumerate(table_lens):
        for j in range(-(-n // PAGE_SIZE)):
            pid = free.pop()
            table[lane, j] = pid
            lo = j * PAGE_SIZE
            w = min(PAGE_SIZE, n - lo)
            pos[pid, 0, :w] = np.arange(lo, lo + w)
            # a mid-page tail holds stale later positions: masked
            pos[pid, 0, w:] = np.arange(n, n + PAGE_SIZE - w)
    return k, v, jnp.asarray(pos), jnp.asarray(table)


def _check_kernels(cfg, seed: int) -> list[str]:
    """Compiled kernels vs kernels/ref.py at the serve's shapes."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    attn = cfg.segments[0].block.attn
    h, hkv, hd = attn.n_heads, attn.n_kv_heads, attn.head_dim
    g = h // hkv
    maxp = CACHE_LEN // PAGE_SIZE
    scale = 1.0 / np.sqrt(hd)
    rng = np.random.default_rng(seed)
    bad = []

    def compare(name, out, want, valid):
        out = np.asarray(out, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.abs(out[valid] - want[valid]).max())
        print(f"kernel {name}: max |kernel - ref| {err:.3e} "
              f"(tolerance {KERNEL_TOL} abs + rel)")
        if not np.allclose(out[valid], want[valid], atol=KERNEL_TOL,
                           rtol=KERNEL_TOL):
            bad.append(f"{name} disagrees with its reference: {err:.3e}")
        if not np.isfinite(out).all() or (out[~valid] != 0).any():
            bad.append(f"{name}: padded rows not exactly zero")

    # decode: one query per lane over its pages; lane 0 is idle
    lens = rng.integers(1, CACHE_LEN + 1, LANES)
    lens[0] = 0
    k, v, pos, table = _random_pool(rng, lens, maxp, hkv, hd)
    q_pos = jnp.asarray(np.maximum(lens - 1, 0), jnp.int32)
    q = jnp.asarray(rng.normal(size=(LANES, h, hd)) * 0.5, jnp.bfloat16)
    out = ops.paged_attention(q, k, v, pos, table, q_pos, scale=scale)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_attention_ref(
            q.reshape(LANES, hkv, g, hd), k, v, pos, table, q_pos,
            jnp.minimum(q_pos // PAGE_SIZE + 1, maxp), scale=scale)
    valid = np.broadcast_to((lens > 0)[:, None, None], out.shape)
    compare("paged_attention", out, want.reshape(LANES, h, hd), valid)

    # prefill: a chunk of up to CHUNK queries after `start` history
    # tokens; width 0 is an idle prefill slot
    starts = rng.integers(0, CACHE_LEN - CHUNK + 1, LANES)
    widths = rng.integers(0, CHUNK + 1, LANES)
    widths[1] = 0
    k, v, pos, table = _random_pool(rng, starts, maxp, hkv, hd)
    c_pos = np.full((LANES, CHUNK), -1, np.int32)
    for lane, (s, w) in enumerate(zip(starts, widths)):
        c_pos[lane, :w] = np.arange(s, s + w)
    c_pos = jnp.asarray(c_pos)
    start = jnp.asarray(starts, jnp.int32)
    q = jnp.asarray(rng.normal(size=(LANES, CHUNK, h, hd)) * 0.5,
                    jnp.bfloat16)
    ck, cv = (jnp.asarray(rng.normal(size=(LANES, CHUNK, hkv, hd)) * 0.5,
                          jnp.bfloat16) for _ in range(2))
    out = ops.paged_prefill(q, k, v, pos, table, c_pos, start, ck, cv,
                            c_pos, scale=scale)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_prefill_ref(
            q.reshape(LANES, CHUNK, hkv, g, hd).transpose(0, 2, 1, 3, 4),
            c_pos, k, v, pos, table, start,
            jnp.clip(-(-start // PAGE_SIZE), 0, maxp),
            ck.transpose(0, 2, 1, 3), cv.transpose(0, 2, 1, 3), c_pos,
            scale=scale)
    want = want.transpose(0, 2, 1, 3, 4).reshape(LANES, CHUNK, h, hd)
    valid = np.broadcast_to((np.asarray(c_pos) >= 0)[:, :, None, None],
                            out.shape)
    compare("paged_prefill", out, want, valid)
    return bad


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, the workload and the "
                         "kernel-check inputs")
    args = ap.parse_args()

    dev = _require_tpu()
    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.serve import build_strategy
    from repro.models import model as M
    from repro.models.param import materialize
    from repro.serving.obs import Observability
    from repro.serving.runtime.workload import WorkloadSpec, make_workload
    from repro.strategy import Cascade

    cache_dir = use_compile_cache()
    compiles = _CompileLog()
    print(f"device: {dev.platform} {dev.device_kind} "
          f"(x{len(jax.devices())}); compile cache {cache_dir}")

    cfg = get_config("paper-ee-100m", smoke=False)
    key = jax.random.PRNGKey(args.seed)
    t0 = time.perf_counter()
    params = materialize(M.model_defs(cfg), key)
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    casc = Cascade.calibrate(params, cfg, key, LAM, solve=False)
    bank = (build_strategy("recall_index", casc, threshold=0.4,
                           patience=2),)
    print(f"model: {cfg.name}, {n_params:,} parameters, "
          f"{cfg.n_ramps + 1} nodes; init + calibration "
          f"{time.perf_counter() - t0:.2f}s")
    compiles.report("init + calibration")

    spec = WorkloadSpec(rate=RATE, duration=DURATION, prompt_len=PROMPT_LEN,
                        vocab=cfg.vocab, max_tokens=MAX_TOKENS,
                        seed=args.seed, strategy="recall_index")
    requests = make_workload("poisson", spec)
    n_tokens = sum(r.max_tokens for r in requests)
    print(f"workload: {len(requests)} requests, {PROMPT_LEN}-token "
          f"prompts, {n_tokens} decode tokens; {LANES} lanes, pages of "
          f"{PAGE_SIZE}, {CHUNK}-token prefill chunks")

    failures = []
    obs = Observability()
    t0 = time.perf_counter()
    m_kernel, pool = _serve(params, cfg, bank, requests, paged_kernel=True,
                            obs=obs)
    wall = time.perf_counter() - t0
    serve_s = m_kernel.t_end - m_kernel.t_start
    print(f"serve (Pallas kernels; one smoke run, not a benchmark): "
          f"{serve_s:.3f}s serving after {wall - serve_s:.2f}s warm-up, "
          f"{m_kernel.steps} steps")
    compiles.report("serve, Pallas kernels")
    nodes = [dict(ev.data)["node"] for ev in obs.tracer.events
             if ev.kind == "token"]
    hist = np.bincount(nodes, minlength=cfg.n_ramps + 1)
    print(f"served-node histogram: {hist.tolist()}")
    failures += _check_serve(m_kernel, pool, requests, cfg.vocab)
    mem = dev.memory_stats() or {}
    print(f"peak_bytes_in_use after the kernel serve: "
          f"{mem.get('peak_bytes_in_use', 'not reported')}")

    m_gather, pool = _serve(params, cfg, bank, requests, paged_kernel=False)
    compiles.report("serve, jnp page gather")
    failures += _check_serve(m_gather, pool, requests, cfg.vocab)
    same = total = 0
    for req in requests:
        a = m_kernel.records[req.rid].tokens
        b = m_gather.records[req.rid].tokens
        same += sum(x == y for x, y in zip(a, b))
        total += max(len(a), len(b))
    print(f"token agreement, kernel vs gather path: {same}/{total} "
          f"({same / max(total, 1):.3f}; random weights: information "
          "only)")

    failures += _check_kernels(cfg, args.seed)
    compiles.report("kernel checks")
    mem = dev.memory_stats() or {}
    print(f"peak_bytes_in_use at exit: "
          f"{mem.get('peak_bytes_in_use', 'not reported')}")

    if failures:
        for f in failures:
            print(f"FAILED: {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
