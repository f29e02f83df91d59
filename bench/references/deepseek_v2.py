"""Plain reference of DeepSeek-V2 (-Lite) with early-exit nodes, held to
one chip's share of the routed experts, and its weights.

The architecture, as DeepSeek-V2's published equations give it: pre-norm
blocks of RMSNorm, Multi-head Latent Attention and an MLP.  Attention
projects the token to queries (no q_lora) and to a latent c_kv (RMSNorm
``kv_norm``) plus one rotary key shared by the heads; keys and values
are c_kv expanded by W_uk and W_uv, here written out (the unabsorbed
form).  Rotary frequencies are YaRN's (factor, original context,
beta_fast, beta_slow) and the softmax scale is (nope + rope)^-1/2 times
mscale(factor, mscale_all_dim)^2.  The first ``first_dense`` layers have
a dense SwiGLU MLP; the rest a mixture of experts: a float32 router over
all ``n_routed_experts``, softmax, the top ``top_k``, gates renormalised
only with ``norm_topk_prob`` and scaled by ``routed_scaling_factor``,
plus the shared experts (one SwiGLU of width ``shared_d_ff``) for every
token.  Of the routed experts only those held here (``experts_first`` ..
+ ``experts_held``) are computed; what the others would add is left
out, as in the program (the chip's share of an expert-parallel
deployment).  The layers are split into segments, the first holding the
dense layers; every segment but the last ends in a ramp (an RMSNorm and
the untied unembedding), the head after the last.  Node i is the
readout after segment i.

Departure, as in the program: the rotary acts on the two halves of the
rope dims (rotate-half) where DeepSeek's code first de-interleaves
them; that is a fixed permutation of the rope columns of W_q and W_dkv,
which random weights do not see.

This file imports nothing of the program.  The weights are drawn here,
from the seed, in one jitted call; `program_params` lays them out as
the program's parameter tree and `plain_params` as this reference's.
Every leaf of layer l is drawn from its own key (leaf, l), and each
routed expert's from (leaf, l, expert id), so a share of the experts
holds the same numbers as the uncut layer and both layouts hold the
same numbers.

`logits` is the full-sequence forward of one request in straightforward
jax.numpy, in float32 (weights are kept in their drawn dtype and upcast
per layer; queries are attended in blocks).  A token that exited at node
d wrote its latent keys only in segments 0..d, so in a deeper segment
later queries do not see it (``depth``, as `dense_decoder` has it); its
own deeper layers are discarded, so it enters no deeper expert that
matters.  c_kv and the rotary key are rounded to the KV dtype, as the
latent pool stores them.  ``compute="float8"`` is the control: every
weight matrix rounded to float8_e4m3 with a scale per output channel,
and every activation entering a matrix product with a scale per row.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["segment_sizes", "program_params", "plain_params", "logits",
           "check_logits", "moe_layer"]

_ATTN = ("wq", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo")
_MLP = ("w_gate", "w_up", "w_down")
_LEAVES = (("norm1", "norm2", "router") + _ATTN
           + tuple("dense_" + n for n in _MLP)
           + tuple("expert_" + n for n in _MLP)
           + tuple("shared_" + n for n in _MLP))
_Q_BLOCK = 512


def segment_sizes(n_layers: int, n_segments: int,
                  first_dense: int = 1) -> list[int]:
    """The dense layers, then the rest near-equal, the longer last."""
    rest, n = n_layers - first_dense, n_segments - 1
    base, rem = divmod(rest, n)
    return [first_dense] + [base + (1 if i >= n - rem else 0)
                            for i in range(n)]


def _shapes(s: dict) -> dict:
    d, h = s["d_model"], s["n_heads"]
    nope, rope, v, lora = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                           s["v_head_dim"], s["kv_lora_rank"])
    f, fs, ff = s["moe_d_ff"], s["shared_d_ff"], s["d_ff"]
    return {"norm1": (d,), "norm2": (d,), "router": (d, s["n_routed_experts"]),
            "wq": (d, h * (nope + rope)), "w_dkv": (d, lora + rope),
            "kv_norm": (lora,), "w_uk": (lora, h * nope),
            "w_uv": (lora, h * v), "wo": (h * v, d),
            "dense_w_gate": (d, ff), "dense_w_up": (d, ff),
            "dense_w_down": (ff, d),
            "expert_w_gate": (d, f), "expert_w_up": (d, f),
            "expert_w_down": (f, d),
            "shared_w_gate": (d, fs), "shared_w_up": (d, fs),
            "shared_w_down": (fs, d)}


def _key(seed: int):
    return jax.random.key(seed % (1 << 63))


def _leaf(key, name, shape, dtype, layers, experts=None):
    """Layer-stacked leaf (and expert-stacked for the routed experts):
    normal with std 1/sqrt(fan_in); norm scales 1."""
    if name.startswith("norm") or name == "kv_norm":
        lead = (len(layers),) + (() if experts is None else (len(experts),))
        return jnp.ones(lead + shape, dtype)
    k = jax.random.fold_in(key, _LEAVES.index(name))
    std = 1.0 / math.sqrt(shape[0])

    def one(lk):
        return (std * jax.random.normal(lk, shape, jnp.float32)).astype(dtype)

    def layer(li):
        lk = jax.random.fold_in(k, li)
        if experts is None:
            return one(lk)
        return jax.lax.map(lambda e: one(jax.random.fold_in(lk, e)),
                           jnp.asarray(experts, jnp.int32))

    return jax.lax.map(layer, jnp.asarray(layers, jnp.int32))


def _table(key, s, dtype, name, shape, std):
    k = jax.random.fold_in(key, len(_LEAVES) + name)
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def _embed(key, s, dtype):
    return _table(key, s, dtype, 0, (s["vocab"], s["d_model"]), 0.02)


def _unembed(key, s, dtype):
    return _table(key, s, dtype, 1, (s["d_model"], s["vocab"]),
                  1.0 / math.sqrt(s["d_model"]))


def _held(s: dict) -> list[int]:
    first = s.get("experts_first", 0)
    return list(range(first, first + s["experts_held"]))


def program_params(s: dict, seed: int, dtype):
    """The program's parameter tree (`repro.models.model.model_defs`
    layout), drawn on the device in one jitted call."""
    shapes = _shapes(s)
    sizes = segment_sizes(s["n_layers"], s["n_segments"],
                          s.get("first_dense", 1))
    d, held = s["d_model"], _held(s)

    def build(key):
        segs, first = [], 0
        for i, n in enumerate(sizes):
            layers = list(range(first, first + n))
            first += n

            def lv(name, experts=None, layers=layers):
                return _leaf(key, name, shapes[name], dtype, layers, experts)

            blocks = {"norm1": {"scale": lv("norm1")},
                      "attn": {n_: lv(n_) for n_ in _ATTN},
                      "norm2": {"scale": lv("norm2")}}
            if layers[0] < s.get("first_dense", 1):
                blocks["mlp"] = {n_: lv("dense_" + n_) for n_ in _MLP}
            else:
                moe = {n_: lv("expert_" + n_, held) for n_ in _MLP}
                moe["router"] = lv("router")
                moe["shared"] = {n_: lv("shared_" + n_) for n_ in _MLP}
                blocks["moe"] = moe
            seg = {"blocks": blocks}
            if i < len(sizes) - 1:
                seg["ramp"] = {"norm": {"scale": jnp.ones((d,), dtype)}}
            segs.append(seg)
        return {"embed": {"table": _embed(key, s, dtype)}, "segments": segs,
                "final_norm": {"scale": jnp.ones((d,), dtype)},
                "unembed": _unembed(key, s, dtype)}

    return jax.jit(build)(_key(seed))


def plain_params(s: dict, seed: int, dtype):
    """The same numbers in this reference's layout: attention and norms
    stacked over every layer, dense MLPs over the dense layers, expert
    leaves over the MoE layers (and the held experts), ramp norms
    stacked with the head's last."""
    shapes = _shapes(s)
    n_dense = s.get("first_dense", 1)
    layers = list(range(s["n_layers"]))
    moe_layers = layers[n_dense:]
    held = _held(s)

    def build(key):
        def lv(name, at, experts=None):
            return _leaf(key, name, shapes[name], dtype, at, experts)

        return {"embed": _embed(key, s, dtype),
                "unembed": _unembed(key, s, dtype),
                "attn": {n: lv(n, layers) for n in _ATTN
                         + ("norm1", "norm2")},
                "dense": {n: lv("dense_" + n, layers[:n_dense])
                          for n in _MLP},
                "moe": dict({n: lv("expert_" + n, moe_layers, held)
                             for n in _MLP},
                            router=lv("router", moe_layers),
                            **{"shared_" + n: lv("shared_" + n, moe_layers)
                               for n in _MLP}),
                "node_norm": jnp.ones((s["n_segments"], s["d_model"]),
                                      dtype)}

    return jax.jit(build)(_key(seed))


def _round_fp8(x, axis):
    """Round to float8_e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _mscale(scale: float, m: float) -> float:
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: dict | None) -> np.ndarray:
    """DeepSeek-V2's YaRN frequencies for a rotary of ``dim`` dims
    (float64): inv_freq = freq_inter (1 - m) + freq_extra m, freq_extra
    = theta^(-2i/dim), freq_inter = freq_extra / factor, m = 1 - the
    linear ramp from floor(c(beta_fast)) to ceil(c(beta_slow)), c(r) =
    dim ln(original / (2 pi r)) / (2 ln theta)."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    if yarn is None:
        return extra

    def c(turns):
        return dim * math.log(yarn["original_max_position"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(c(yarn["beta_fast"])), 0)
    high = min(math.ceil(c(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return extra / yarn["factor"] * (1 - mask) + extra * mask


def _rope(x, pos, s):
    """Rotate-half rotary over the last axis of x (S, ..., rope)."""
    yarn = s.get("yarn")
    freqs = jnp.asarray(yarn_inv_freq(x.shape[-1], s["rope_theta"], yarn),
                        jnp.float32)
    m = 1.0 if yarn is None else (_mscale(yarn["factor"], yarn["mscale"])
                                  / _mscale(yarn["factor"],
                                            yarn["mscale_all_dim"]))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c = (m * jnp.cos(ang)).reshape(ang.shape[:1] + (1,) * (x.ndim - 2)
                                   + ang.shape[1:])
    s_ = (m * jnp.sin(ang)).reshape(c.shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s_, x1 * s_ + x2 * c], -1)


def softmax_scale(s: dict) -> float:
    base = (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]) ** -0.5
    yarn = s.get("yarn")
    if yarn is None:
        return base
    return base * _mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2


def _ops(compute: str):
    """(weight, activation, matmul) of the given precision."""
    def w(x):
        x = x.astype(jnp.float32)
        return _round_fp8(x, 0) if compute == "float8" else x

    def a(x):
        x = x.astype(jnp.float32)
        return _round_fp8(x, -1) if compute == "float8" else x

    def mm(x, wt):
        return jnp.dot(a(x), w(wt), preferred_element_type=jnp.float32)
    return w, a, mm


def _swiglu(mm, x, wg, wu, wd):
    g = mm(x, wg)
    return mm(g * jax.nn.sigmoid(g) * mm(x, wu), wd)


def moe_layer(s: dict, lw: dict, x, *, compute: str = "float32"):
    """The held experts' part of one MoE layer plus its shared experts,
    for rows x (N, D) already normed; lw holds that layer's ``router``,
    ``w_gate/w_up/w_down`` (held, ...) and ``shared_*`` leaves."""
    _, a, mm = _ops(compute)
    probs = jax.nn.softmax(mm(x, lw["router"]), -1)
    gates, chosen = jax.lax.top_k(probs, s["top_k"])
    if s["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    gates = gates * s["routed_scaling_factor"]
    y = _swiglu(mm, x, lw["shared_w_gate"], lw["shared_w_up"],
                lw["shared_w_down"])
    for j, e in enumerate(_held(s)):
        g = jnp.where(chosen == e, gates, 0.0).sum(-1)        # (N,)
        y = y + g[:, None] * _swiglu(mm, x, lw["w_gate"][j], lw["w_up"][j],
                                     lw["w_down"][j])
    return y


def logits(plain, s: dict, tokens, depth, read_pos, read_node, *,
           kv_dtype=jnp.bfloat16, compute: str = "float32"):
    """Readout logits of one request.

    tokens (S,) i32 inputs by position (padding anything); depth (S,)
    i32 the last node each position ran (-1 for padding: never a key);
    read_pos, read_node (R,) i32 where to read which node's logits.
    Returns (R, vocab) f32; rows with a node outside [0, n_segments)
    are zero."""
    n_seg = s["n_segments"]
    n_dense = s.get("first_dense", 1)
    sizes = segment_sizes(s["n_layers"], n_seg, n_dense)
    h, nope, rope, vd, lora = (s["n_heads"], s["qk_nope_head_dim"],
                               s["qk_rope_head_dim"], s["v_head_dim"],
                               s["kv_lora_rank"])
    eps = s["norm_eps"]
    scale = softmax_scale(s)
    w, a, mm = _ops(compute)
    seq = tokens.shape[0]
    pos = jnp.arange(seq, dtype=jnp.int32)
    qb = min(_Q_BLOCK, seq)
    nq = -(-seq // qb)

    def attention(x, lw, keep):
        y = _rms(x, lw["norm1"], eps)
        q = mm(y, lw["wq"]).reshape(seq, h, nope + rope)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, s)
        dkv = mm(y, lw["w_dkv"])
        c_kv = _rms(dkv[:, :lora], lw["kv_norm"], eps)
        k_rope = _rope(dkv[:, lora:], pos, s)
        # the latent pool's rounding
        c_kv = c_kv.astype(kv_dtype).astype(jnp.float32)
        k_rope = k_rope.astype(kv_dtype).astype(jnp.float32)
        k_nope = mm(c_kv, lw["w_uk"]).reshape(seq, h, nope)
        v = mm(c_kv, lw["w_uv"]).reshape(seq, h, vd)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, None], (seq, h, rope))], -1)
        qf = jnp.concatenate([q_nope, q_rope], -1)
        qf = jnp.pad(qf, ((0, nq * qb - seq), (0, 0), (0, 0)))

        def block(i):
            qi = jax.lax.dynamic_slice_in_dim(qf, i * qb, qb)
            qpos = i * qb + jnp.arange(qb)
            sc = jnp.einsum("qhd,thd->hqt", a(qi), a(k),
                            preferred_element_type=jnp.float32) * scale
            ok = (pos[None, :] <= qpos[:, None]) & keep[None, :]
            sc = jnp.where(ok[None], sc, -1e30)
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("hqt,thd->qhd", a(p), a(v),
                              preferred_element_type=jnp.float32)

        o = jax.lax.map(block, jnp.arange(nq)).reshape(nq * qb, h * vd)
        return x + mm(o[:seq], lw["wo"])

    def dense_layer(x, lw, keep):
        x = attention(x, lw, keep)
        y = _rms(x, lw["norm2"], eps)
        return x + _swiglu(mm, y, lw["w_gate"], lw["w_up"], lw["w_down"])

    def moe_block(x, lw, keep):
        x = attention(x, lw, keep)
        return x + moe_layer(s, lw, _rms(x, lw["norm2"], eps),
                             compute=compute)

    def layers_of(tree, lo, hi):
        return jax.tree.map(lambda t: t[lo:hi], tree)

    x = plain["embed"][tokens].astype(jnp.float32)
    out = jnp.zeros((read_pos.shape[0], s["vocab"]), jnp.float32)
    first = 0
    for si, n in enumerate(sizes):
        keep = depth >= si
        att = layers_of(plain["attn"], first, first + n)
        if first < n_dense:
            lw_seg = dict(att, **layers_of(plain["dense"], first, first + n))
            body = dense_layer
        else:
            m = layers_of(plain["moe"], first - n_dense, first - n_dense + n)
            lw_seg = dict(att, **m)
            body = moe_block
        first += n
        x, _ = jax.lax.scan(
            lambda x, lw, keep=keep, body=body: (body(x, lw, keep), None),
            x, lw_seg)
        hn = _rms(x[read_pos], plain["node_norm"][si], eps)
        lg = jnp.dot(a(hn), w(plain["unembed"]),
                     preferred_element_type=jnp.float32)
        out = jnp.where((read_node == si)[:, None], lg, out)
    return out


def check_logits(plain, s, tokens, depth, read_pos, read_node, served, *,
                 kv_dtype=jnp.bfloat16, control: str | None = None):
    """Per readout row: the gap by which the served token's logit lies
    below the reference's best, and (with ``control``) the gap of the
    token that the lower-precision forward puts first.  Jitted per
    shape by the caller."""
    with jax.default_matmul_precision("highest"):
        ref = logits(plain, s, tokens, depth, read_pos, read_node,
                     kv_dtype=kv_dtype)
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[:, None], 1)[:, 0]
    if control is None:
        return gap, None
    low = logits(plain, s, tokens, depth, read_pos, read_node,
                 kv_dtype=kv_dtype, compute=control)
    pick = jnp.argmax(low, -1)
    return gap, best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
