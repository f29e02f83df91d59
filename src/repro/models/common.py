"""Shared low-level layers: RMSNorm, RoPE, embeddings, masks."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.param import ParamDef

__all__ = ["rms_norm", "rms_norm_def", "rope", "rope_cos_sin",
           "yarn_freqs", "yarn_mscale", "causal_mask", "embed_def"]


def rms_norm_def(dim: int, axis: str = "embed") -> dict:
    return {"scale": ParamDef((dim,), (axis,), init="ones")}


def rms_norm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def embed_def(vocab: int, d_model: int) -> dict:
    return {"table": ParamDef((vocab, d_model), ("vocab", "embed"),
                              init="embed", scale=0.02)}


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 m ln(s) + 1 (1 for s <= 1)."""
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_freqs(head_dim: int, theta: float, yarn) -> np.ndarray:
    """The head_dim // 2 rotary frequencies under YaRN (a
    `config.YarnConfig`; float64 host constants), as DeepSeek-V2 blends
    them: the plain frequencies and those divided by the factor, by a
    linear ramp between the dims that turn beta_fast and beta_slow
    times over the original context."""
    half = head_dim // 2
    extra = 1.0 / theta ** (np.arange(half) * 2.0 / head_dim)

    def dim_of(turns):
        return (head_dim * math.log(yarn.original_max_position
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(half) - low) / (high - low), 0, 1)
    return extra / yarn.factor * (1 - keep) + extra * keep


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float,
                 yarn=None) -> tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin of shape (..., head_dim//2); with
    ``yarn`` the YaRN frequencies, cos and sin scaled by
    mscale(mscale) / mscale(mscale_all_dim)."""
    if yarn is None:
        half = head_dim // 2
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                 / half))
    else:
        freqs = jnp.asarray(yarn_freqs(head_dim, theta, yarn), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    return cos, sin


def rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Apply rotary embedding.  x: (..., seq, heads, head_dim);
    cos/sin: (..., seq, head_dim//2) — broadcast over the heads axis."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           axis=-1).astype(x.dtype)


def causal_mask(q_pos: jax.Array, kv_pos: jax.Array,
                window: int | None = None) -> jax.Array:
    """Boolean (..., q, kv) mask: True = attend.

    q_pos (..., q), kv_pos (..., kv) are absolute positions; a sliding
    window additionally requires kv_pos > q_pos - window.
    """
    m = kv_pos[..., None, :] <= q_pos[..., :, None]
    if window is not None:
        m &= kv_pos[..., None, :] > (q_pos[..., :, None] - window)
    return m
