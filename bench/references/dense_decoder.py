"""Plain reference of the dense early-exit decoder, and its weights.

Both configurations of the benchmark (paper-ee-100m, granite-3-2b) are
this architecture: pre-norm blocks of RMSNorm, grouped-query attention
with rotary positions and no biases, and a GELU or SwiGLU MLP; the
layers are split into segments and every segment but the last ends in
a ramp (an RMSNorm plus the tied unembedding), the head after the last.
Node i is the readout after segment i.

This file imports nothing of the program.  The weights are drawn here,
from the seed, in one jitted call; `program_params` lays them out as
the program's parameter tree and `plain_params` as this reference's.
Every leaf of layer l is drawn from its own key (leaf, l), so both
layouts hold the same numbers.

`logits` is the full-sequence forward of one request in straightforward
jax.numpy, in float32 at the highest matmul precision.  A token that
exited at node d wrote its keys and values only in segments 0..d, so in
a deeper segment later queries do not see it: the per-position ``depth``
argument says how deep each position ran, and keys of segment s are
masked where depth < s.  The keys and values are rounded to the
configuration's KV dtype, as the pool stores them.

``compute="bfloat16"`` and ``compute="float8"`` compute the same
forward in a lower precision: the control of the correctness check.
bfloat16 casts weights and activations to bfloat16; float8 rounds every
weight matrix to float8_e4m3 with a scale per output channel, and every
activation entering a matrix product to float8_e4m3 with a scale per
row, the usual fp8 recipe.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["segment_sizes", "program_params", "plain_params", "logits",
           "check_logits"]

_LAYER_LEAVES = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_up",
                 "w_down", "w_gate")


def segment_sizes(n_layers: int, n_segments: int) -> list[int]:
    """Layers per segment: near-equal, the longer ones last."""
    base, rem = divmod(n_layers, n_segments)
    return [base + (1 if i >= n_segments - rem else 0)
            for i in range(n_segments)]


def _layer_shapes(s: dict) -> dict:
    d, h, kv, hd, ff = (s["d_model"], s["n_heads"], s["n_kv_heads"],
                        s["head_dim"], s["d_ff"])
    shapes = {"norm1": (d,), "wq": (d, h * hd), "wk": (d, kv * hd),
              "wv": (d, kv * hd), "wo": (h * hd, d), "norm2": (d,),
              "w_up": (d, ff), "w_down": (ff, d)}
    if s["act"] == "swiglu":
        shapes["w_gate"] = (d, ff)
    return shapes


def _leaf(key, name: str, layers, shape, dtype):
    """Layer-stacked leaf for the given layer indices."""
    if name.startswith("norm"):
        return jnp.ones((len(layers),) + shape, dtype)
    k = jax.random.fold_in(key, _LAYER_LEAVES.index(name))
    std = 1.0 / math.sqrt(shape[0])

    def one(layer):
        return (std * jax.random.normal(jax.random.fold_in(k, layer), shape,
                                        jnp.float32)).astype(dtype)

    return jax.vmap(one)(jnp.asarray(layers, jnp.int32))


def _embed(key, s: dict, dtype):
    k = jax.random.fold_in(key, len(_LAYER_LEAVES))
    return (0.02 * jax.random.normal(k, (s["vocab"], s["d_model"]),
                                     jnp.float32)).astype(dtype)


def _key(seed: int):
    return jax.random.key(seed % (1 << 63))


def program_params(s: dict, seed: int, dtype):
    """The program's parameter tree (`repro.models.model.model_defs`
    layout), drawn on the device in one jitted call."""
    shapes = _layer_shapes(s)
    sizes = segment_sizes(s["n_layers"], s["n_segments"])
    d = s["d_model"]

    def build(key):
        segs, first = [], 0
        for i, n in enumerate(sizes):
            layers = list(range(first, first + n))
            first += n
            lv = {name: _leaf(key, name, layers, shape, dtype)
                  for name, shape in shapes.items()}
            mlp = {"w_up": lv["w_up"], "w_down": lv["w_down"]}
            if "w_gate" in lv:
                mlp["w_gate"] = lv["w_gate"]
            seg = {"blocks": {
                "norm1": {"scale": lv["norm1"]},
                "attn": {n_: lv[n_] for n_ in ("wq", "wk", "wv", "wo")},
                "norm2": {"scale": lv["norm2"]},
                "mlp": mlp}}
            if i < len(sizes) - 1:
                seg["ramp"] = {"norm": {"scale": jnp.ones((d,), dtype)}}
            segs.append(seg)
        return {"embed": {"table": _embed(key, s, dtype)}, "segments": segs,
                "final_norm": {"scale": jnp.ones((d,), dtype)}}

    return jax.jit(build)(_key(seed))


def plain_params(s: dict, seed: int, dtype):
    """The same numbers in this reference's layout: every layer stacked
    along one axis, ramp norms stacked, the head's norm last."""
    shapes = _layer_shapes(s)
    d, n_seg = s["d_model"], s["n_segments"]

    def build(key):
        layers = list(range(s["n_layers"]))
        return {"embed": _embed(key, s, dtype),
                "layers": {name: _leaf(key, name, layers, shape, dtype)
                           for name, shape in shapes.items()},
                "node_norm": jnp.ones((n_seg, d), dtype)}

    return jax.jit(build)(_key(seed))


def _round_fp8(x, axis):
    """Round to float8_e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, eps):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (S, half)
    c, s_ = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s_, x1 * s_ + x2 * c], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def logits(plain, s: dict, tokens, depth, read_pos, read_node, *,
           kv_dtype=jnp.bfloat16, compute: str = "float32"):
    """Readout logits of one request.

    tokens (S,) i32 inputs by position (padding anything); depth (S,)
    i32 the last node each position ran (-1 for padding: never a key);
    read_pos, read_node (R,) i32 where to read which node's logits.
    Returns (R, vocab) f32; rows with a node outside [0, n_segments)
    are zero."""
    n_seg = s["n_segments"]
    sizes = segment_sizes(s["n_layers"], n_seg)
    h, kv, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    g = h // kv
    eps = s["norm_eps"]
    act_dt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
              "float8": jnp.float32}[compute]

    def w(x):
        if compute == "float8":
            return _round_fp8(x.astype(jnp.float32), 0)
        return x.astype(act_dt)

    def a(x):
        if compute == "float8":
            return _round_fp8(x.astype(jnp.float32), -1)
        return x.astype(act_dt)

    def mm(x, wt):
        return jnp.dot(a(x), w(wt),
                       preferred_element_type=jnp.float32).astype(act_dt)

    def norm(x, scale):
        return (_rms(x, eps) * scale.astype(jnp.float32)).astype(act_dt)

    seq = tokens.shape[0]
    pos = jnp.arange(seq, dtype=jnp.int32)
    causal = pos[None, :] <= pos[:, None]
    embed = plain["embed"]
    x = embed[tokens].astype(act_dt)

    def layer(x, lw, mask):
        y = norm(x, lw["norm1"])
        q = mm(y, lw["wq"]).reshape(seq, h, hd)
        k = mm(y, lw["wk"]).reshape(seq, kv, hd)
        v = mm(y, lw["wv"]).reshape(seq, kv, hd)
        q = _rope(q, pos, s["rope_theta"]).astype(act_dt)
        k = _rope(k, pos, s["rope_theta"]).astype(kv_dtype).astype(act_dt)
        v = v.astype(kv_dtype).astype(act_dt)
        qg = q.reshape(seq, kv, g, hd)
        sc = jnp.einsum("skgd,tkd->kgst", a(qg), a(k),
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
        sc = jnp.where(mask[None, None], sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1).astype(act_dt)
        o = jnp.einsum("kgst,tkd->skgd", a(p), a(v),
                       preferred_element_type=jnp.float32).astype(act_dt)
        x = x + mm(o.reshape(seq, h * hd), lw["wo"])
        y = norm(x, lw["norm2"])
        if s["act"] == "swiglu":
            gate = mm(y, lw["w_gate"]).astype(jnp.float32)
            hid = (gate * jax.nn.sigmoid(gate)
                   * mm(y, lw["w_up"]).astype(jnp.float32)).astype(act_dt)
        else:
            hid = _gelu_tanh(mm(y, lw["w_up"]).astype(jnp.float32)).astype(
                act_dt)
        return x + mm(hid, lw["w_down"])

    out = jnp.zeros((read_pos.shape[0], s["vocab"]), jnp.float32)
    first = 0
    for si, n in enumerate(sizes):
        mask = causal & (depth >= si)[None, :]
        seg_w = jax.tree.map(lambda t, first=first, n=n: t[first:first + n],
                             plain["layers"])
        first += n
        x, _ = jax.lax.scan(lambda x, lw, mask=mask: (layer(x, lw, mask),
                                                      None), x, seg_w)
        hn = norm(x[read_pos], plain["node_norm"][si])
        lg = jnp.dot(a(hn), w(embed.T), preferred_element_type=jnp.float32)
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
