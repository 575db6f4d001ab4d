"""``--arch <id>`` resolution. Import is lazy so configs stay cheap."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import ArchConfig

# arch_id -> (module, attr)
_REGISTRY: Dict[str, tuple] = {
    # LM family (assigned)
    "qwen3-1.7b": ("repro.configs.qwen3_1p7b", "ARCH"),
    "h2o-danube-1.8b": ("repro.configs.h2o_danube_1p8b", "ARCH"),
    "qwen2-1.5b": ("repro.configs.qwen2_1p5b", "ARCH"),
    "qwen2-moe-a2.7b": ("repro.configs.qwen2_moe_a2p7b", "ARCH"),
    "llama4-scout-17b-a16e": ("repro.configs.llama4_scout_17b_a16e", "ARCH"),
    # GNN (assigned)
    "graphsage-reddit": ("repro.configs.graphsage_reddit", "ARCH"),
    # RecSys (assigned)
    "dlrm-rm2": ("repro.configs.dlrm_rm2", "ARCH"),
    "sasrec": ("repro.configs.sasrec", "ARCH"),
    "dcn-v2": ("repro.configs.dcn_v2", "ARCH"),
    "wide-deep": ("repro.configs.wide_deep", "ARCH"),
}

ASSIGNED_ARCHS: List[str] = [
    "qwen3-1.7b", "h2o-danube-1.8b", "qwen2-1.5b", "qwen2-moe-a2.7b",
    "llama4-scout-17b-a16e", "graphsage-reddit", "dlrm-rm2", "sasrec",
    "dcn-v2", "wide-deep",
]


def get_arch(arch_id: str) -> ArchConfig:
    try:
        mod_name, attr = _REGISTRY[arch_id]
    except KeyError:
        raise KeyError(
            f"unknown arch {arch_id!r}; known: {sorted(_REGISTRY)}") from None
    return getattr(importlib.import_module(mod_name), attr)
