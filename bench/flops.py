"""The work each kernel call and each step needs, counted from shapes.

Only real rows and real pages count: lanes that decode or prefill in
that segment, keys up to each query's position, the pages a lane holds.
Padding, idle lanes and masked rows are never counted, so a share of a
roofline or a peak reads the same work whatever computes it.

Matrix parameters follow the usual 2 x parameters per token (the
embedding lookup is free); attention adds 4 x heads x head_dim per
(query, key) pair (QK^T and PV).  Each readout a token probes (a ramp,
or the head) pays its unembedding, 2 x d_model x vocab: the analytic
count of `repro.launch.flops` counts the unembedding once, but here
every probed node reads one out.
"""

from __future__ import annotations

__all__ = ["layer_params", "decode_attention", "prefill_attention",
           "step_flops", "kernel_work", "roofline", "split_steps",
           "step_mfu"]


def layer_params(s: dict) -> int:
    d, h, kv, hd, ff = (s["d_model"], s["n_heads"], s["n_kv_heads"],
                        s["head_dim"], s["d_ff"])
    mlp = (3 if s["act"] == "swiglu" else 2) * d * ff
    return d * h * hd * 2 + d * kv * hd * 2 + mlp


def _pages(n_tokens: int, page: int) -> int:
    return -(-n_tokens // page)


def decode_attention(s: dict, ctx_lens, *, page: int, kv_bytes: int,
                     act_bytes: int) -> tuple[float, float]:
    """(flops, bytes) of one paged-attention call: one query per lane,
    ``ctx_lens`` keys each (its own included)."""
    h, kv, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    flops = sum(4.0 * h * hd * n for n in ctx_lens)
    by = 0.0
    for n in ctx_lens:
        p = _pages(n, page)
        by += p * page * (2 * kv * hd * kv_bytes + 4)   # k, v and positions
        by += 2 * h * hd * act_bytes                    # q in, out
    return flops, by


def prefill_attention(s: dict, chunks, *, page: int, kv_bytes: int,
                      act_bytes: int) -> tuple[float, float]:
    """(flops, bytes) of one paged-prefill call: ``chunks`` holds
    (start, width) per prefilling lane; the chunk's queries attend to
    the ``start`` history keys in the pool and causally to each other."""
    h, kv, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    flops = by = 0.0
    for start, w in chunks:
        pairs = w * start + w * (w + 1) / 2
        flops += 4.0 * h * hd * pairs
        p = _pages(start, page)
        by += p * page * (2 * kv * hd * kv_bytes + 4)   # history pages
        by += w * (2 * h * hd + 2 * kv * hd) * act_bytes  # q, out, k, v
        by += w * 4                                     # query positions
    return flops, by


def step_flops(s: dict, sizes, decode, chunks) -> float:
    """Model FLOPs one fused step needs.

    decode: (ctx_len, node) per decoding lane -- it ran segments
    0..node and read out nodes 0..node; chunks: (start, width, emits)
    per prefilling lane -- full depth, and one head readout when the
    chunk finishes the prompt."""
    h, hd = s["n_heads"], s["head_dim"]
    lp = layer_params(s)
    readout = 2.0 * s["d_model"] * s["vocab"]
    total = 0.0
    for ctx, node in decode:
        layers = sum(sizes[:node + 1])
        total += layers * (2.0 * lp + 4.0 * h * hd * ctx)
        total += (node + 1) * readout
    n_layers = sum(sizes)
    for start, w, emits in chunks:
        pairs = w * start + w * (w + 1) / 2
        total += n_layers * (2.0 * lp * w + 4.0 * h * hd * pairs)
        total += readout if emits else 0.0
    return total


def _act_bytes(rec) -> int:
    return 4 if rec["config"]["weight_dtype"] == "float32" else 2


def _kv_bytes(rec) -> int:
    return 2 if rec["config"]["kv_dtype"] == "bfloat16" else 4


def kernel_work(rec, kernel: str) -> tuple[float, float]:
    """(flops, bytes) the traced steps needed of ``kernel``: one call
    per layer run, over the lanes that ran that layer."""
    s, sizes = rec["sizes"], rec["segment_sizes"]
    page = rec["config"]["page_size"]
    kw = {"page": page, "kv_bytes": _kv_bytes(rec),
          "act_bytes": _act_bytes(rec)}
    fl = by = 0.0
    for st in rec["traced_steps"]:
        if kernel == "paged_attention":
            for ctx, node in st["decode"]:
                f, b = decode_attention(s, [ctx], **kw)
                n = sum(sizes[:node + 1])
                fl, by = fl + n * f, by + n * b
        elif kernel == "paged_prefill" and st["chunks"]:
            f, b = prefill_attention(
                s, [(a, w) for a, w, _ in st["chunks"]], **kw)
            fl, by = fl + sum(sizes) * f, by + sum(sizes) * b
    return fl, by


def roofline(rec, kernel: str) -> tuple[float, str] | None:
    """(share of the roofline in %, which bound) of a kernel over the
    traced steps, or None when the trace has no time for it."""
    secs = rec["trace"]["kernels"].get(kernel)
    if not secs or rec["peak"] is None:
        return None
    fl, by = kernel_work(rec, kernel)
    if fl == 0:
        return None
    t_c = fl / rec["peak"]["flops"]
    t_m = by / rec["peak"]["hbm_bytes_s"]
    return 100.0 * max(t_c, t_m) / secs, ("compute" if t_c >= t_m
                                          else "memory")


def split_steps(rec, chunk: bool):
    """(steps, device seconds each) of the traced steps that did (or
    did not) carry a prefill chunk; None when the trace's step programs
    do not pair one to one with the recorded steps."""
    dev = rec["trace"]["step_device_s"]
    steps = rec["traced_steps"]
    if dev is None or len(dev) != len(steps):
        return None
    pick = [(st, t) for st, t in zip(steps, dev)
            if bool(st["chunks"]) == chunk]
    return pick or None


def step_mfu(rec, chunk: bool) -> float | None:
    pick = split_steps(rec, chunk)
    if pick is None or rec["peak"] is None:
        return None
    s, sizes = rec["sizes"], rec["segment_sizes"]
    need = sum(step_flops(s, sizes, st["decode"], st["chunks"])
               for st, _ in pick)
    secs = sum(t for _, t in pick)
    return 100.0 * need / (secs * rec["peak"]["flops"])
