"""Assigned-architecture registry.

Every architecture is selectable as ``--arch <id>``; each file carries the
exact assigned config plus a REDUCED smoke variant (<=2 layers,
d_model<=512, <=4 experts) used by CPU tests.  Full configs are exercised
only via the dry-run (ShapeDtypeStruct, no allocation).
"""
from __future__ import annotations

import importlib

ARCH_IDS = (
    "qwen2.5-3b",
    "smollm-360m",
    "qwen3-32b",
    "recurrentgemma-2b",
    "pixtral-12b",
    "seamless-m4t-medium",
    "gemma3-27b",
    "llama4-maverick-400b-a17b",
    "qwen2-moe-a2.7b",
    "mamba2-2.7b",
    "bmoe-paper",            # the paper's own MoE setup at LM scale
    "moonlight-16b-a3b",
)

_MODULES = {
    "qwen2.5-3b": "qwen2_5_3b",
    "smollm-360m": "smollm_360m",
    "qwen3-32b": "qwen3_32b",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "pixtral-12b": "pixtral_12b",
    "seamless-m4t-medium": "seamless_m4t_medium",
    "gemma3-27b": "gemma3_27b",
    "llama4-maverick-400b-a17b": "llama4_maverick",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "mamba2-2.7b": "mamba2_2_7b",
    "bmoe-paper": "bmoe_paper",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}


def get_config(arch_id: str, smoke: bool = False):
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    cfg = mod.SMOKE if smoke else mod.CONFIG
    if smoke and (cfg.train_microbatches != 1 or cfg.padded_num_experts):
        import dataclasses
        cfg = dataclasses.replace(cfg, train_microbatches=1,
                                  padded_num_experts=0)
    return cfg.validate()
