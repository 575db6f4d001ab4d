"""Bytes a hop must move, from the configuration's sizes alone.

A hop expands one node per frontier slot: it must read that node's chunk
(full vector, degree, R neighbour ids, R inline PQ codes: the paper's
B_AiSAQ) and write the node's exact distance and its R neighbours' ids and
ADC distances. The LUT is not counted: a kernel may keep it resident.
ADC does about one add per byte read, far below the chip's FLOP/byte
ridge, so these bytes bound the kernel.
"""
from __future__ import annotations


def chunk_bytes(cfg: dict) -> int:
    """B_AiSAQ = b_full + 4 (R + 1) + R * pq_m (paper section 3.1)."""
    b_full = cfg["dim"] * (1 if cfg["data_dtype"] == "uint8" else 4)
    return b_full + 4 * (cfg["R"] + 1) + cfg["R"] * cfg["pq_m"]


def hop_slot_bytes(cfg: dict) -> int:
    """Bytes one frontier slot of one hop moves at the least."""
    return chunk_bytes(cfg) + 4 + 8 * cfg["R"]


def hop_share(cfg: dict, calls: list, hbm_bytes_per_s: float):
    """Least time by HBM bytes over the hop kernel's summed time, in %.

    Every launch counts all nq * w frontier slots, converged queries'
    included, as the kernel's grid does."""
    w = cfg["assumed"]["search"]["w"]
    need = sum(c["hop_launches"] * c["nq"] * w for c in calls) \
        * hop_slot_bytes(cfg)
    spent = sum(c["kernel_ns"].get("hop", 0.0) for c in calls) / 1e9
    if need == 0 or spent <= 0:
        return None
    return 100.0 * (need / hbm_bytes_per_s) / spent
