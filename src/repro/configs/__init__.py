"""Architecture registry: every assigned architecture (plus the paper's
own EE workload) selectable via ``--arch <id>``."""

from __future__ import annotations

from repro.configs import (deepseek_v2_lite_16b, deepseek_v2_lite_ep8,
                           granite_3_2b, hymba_1_5b, mamba2_130m,
                           musicgen_large, paper_ee, phi3_5_moe_42b,
                           phi3_vision_4_2b, qwen3_14b, qwen3_4b,
                           starcoder2_3b)

_MODULES = (
    deepseek_v2_lite_16b, deepseek_v2_lite_ep8, qwen3_4b, qwen3_14b, mamba2_130m, hymba_1_5b,
    phi3_5_moe_42b, granite_3_2b, musicgen_large, starcoder2_3b,
    phi3_vision_4_2b, paper_ee,
)

REGISTRY = {m.ARCH_ID: m for m in _MODULES}
ASSIGNED = [m.ARCH_ID for m in _MODULES
            if m not in (paper_ee, deepseek_v2_lite_ep8)]


def get_config(arch: str, smoke: bool = False):
    if arch not in REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(REGISTRY)}")
    mod = REGISTRY[arch]
    return mod.smoke_config() if smoke else mod.full_config()
