"""Architecture registry: ``--arch <id>`` resolution for launchers/tests."""
from __future__ import annotations

import importlib
from typing import Dict, Union

from repro.configs.base import GNNConfig, ModelConfig

# arch id (as assigned) -> module name
_LM_MODULES = {
    "gemma3-27b": "gemma3_27b",
    "gemma3-1b": "gemma3_1b",
    "qwen2-72b": "qwen2_72b",
    "qwen1.5-32b": "qwen15_32b",
    "whisper-large-v3": "whisper_large_v3",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "qwen2-moe-a2.7b": "qwen2_moe_a27b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b",
    "rwkv6-7b": "rwkv6_7b",
    "hymba-1.5b": "hymba_1_5b",
}
_GNN_MODULES = {
    "graphsage": "graphsage",
    "gcn": "gcn",
    "gat": "gat",
    "gat-products": "gat_products",
}

LM_ARCHS = tuple(_LM_MODULES)
GNN_ARCHS = tuple(_GNN_MODULES)
ALL_ARCHS = LM_ARCHS + GNN_ARCHS


def get_config(arch: str) -> Union[ModelConfig, GNNConfig]:
    mods = dict(_LM_MODULES)
    mods.update(_GNN_MODULES)
    if arch not in mods:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(mods)}")
    mod = importlib.import_module(f"repro.configs.{mods[arch]}")
    return mod.CONFIG


def lm_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in LM_ARCHS}


def gnn_configs() -> Dict[str, GNNConfig]:
    return {a: get_config(a) for a in GNN_ARCHS}
