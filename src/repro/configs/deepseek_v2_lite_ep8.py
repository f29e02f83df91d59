"""deepseek-v2-lite-ep8 [moe, mla] — one chip's share of DeepSeek-V2-Lite
served under 8-way expert parallelism.  [hf:deepseek-ai/DeepSeek-V2-Lite]

Every layer (27) at its published widths: d_model 2048, 16-head MLA
(kv_lora 512, nope 128, rope 64, v 128, no q_lora) with YaRN rotary
(factor 40 over 4096 positions), layer 0 dense SwiGLU (10,944), layers
1-26 MoE with a 64-way router, top-6 softmax gates NOT renormalised,
2 shared experts (2,816) — and, of the 64 routed experts, the 8 that
rank 0 of an 8-way expert-parallel deployment holds (experts 0-7).  The
vocabulary (102,400) is whole and the embeddings untied.

Ramps: a node after the dense layer and after every fifth MoE layer
(segments [1, 5, 5, 5, 5, 6]: 5 ramps and the head).  DeepSeek-V2 has
no ramps; the benchmark's exit profile needs a node per segment.
"""

from __future__ import annotations

from repro.configs.deepseek_v2_lite_16b import deepseek_v2_lite
from repro.models.config import (AttnConfig, MLAConfig, MoEConfig,
                                 YarnConfig)
from repro.models.common import yarn_mscale

ARCH_ID = "deepseek-v2-lite-ep8"

YARN = YarnConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                  beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


def _attn(nope, rope, v, lora, heads):
    return AttnConfig(
        n_heads=heads, n_kv_heads=heads, head_dim=nope + rope,
        mla=MLAConfig(kv_lora_rank=lora, qk_nope_head_dim=nope,
                      qk_rope_head_dim=rope, v_head_dim=v),
        yarn=YARN,
        # DeepSeek-V2's softmax scale: (nope + rope)^-1/2 times the YaRN
        # temperature mscale(factor, mscale_all_dim) squared
        softmax_scale=(nope + rope) ** -0.5
        * yarn_mscale(YARN.factor, YARN.mscale_all_dim) ** 2)


def _moe(routed, held, top_k, d_ff, shared, d_ff_shared):
    return MoEConfig(num_experts=routed, top_k=top_k, d_ff_expert=d_ff,
                     num_shared=shared, d_ff_shared=d_ff_shared,
                     norm_topk_prob=False, held_experts=held)


def full_config():
    return deepseek_v2_lite(
        ARCH_ID, d_model=2048, vocab=102_400,
        attn=_attn(128, 64, 128, 512, 16), dense_ff=10_944,
        moe=_moe(64, 8, 6, 1408, 2, 2816), moe_sizes=[5, 5, 5, 5, 6],
        ramp_after_dense=True, norm_eps=1e-6)


def smoke_config():
    """The same structure at CPU-test size: YaRN MLA, a dense first
    segment, 8 held of 16 routed experts, top-3, a shared expert, a node
    after every segment (two layers, as every smoke config)."""
    return deepseek_v2_lite(
        ARCH_ID + "-smoke", d_model=64, vocab=256,
        attn=_attn(16, 8, 16, 32, 4), dense_ff=96,
        moe=_moe(16, 8, 3, 32, 1, 48), moe_sizes=[1],
        ramp_after_dense=True, norm_eps=1e-6)
