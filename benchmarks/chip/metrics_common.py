"""Arithmetic shared by metric readers."""
from __future__ import annotations

import math

import numpy as np


def latency_percentile(rec, q: float):
    """The q-th percentile (linear interpolation) of all requests' latency;
    None where a failed request (inf) reaches it."""
    lat = rec["window"].latencies_ms
    if lat is None or len(lat) == 0:
        return None
    v = float(np.percentile(np.asarray(lat, np.float64), q))
    return v if math.isfinite(v) else None


def traced(rec):
    """The reduced trace of a ``--trace 1`` run, or None."""
    return rec["window"].trace


def calls(rec):
    """The traced search calls, or None when the run was not traced."""
    t = traced(rec)
    return t["calls"] if t and t["calls"] else None


def batch_size(rec):
    c = calls(rec)
    return None if c is None else sum(x["nq"] for x in c) / len(c)


def search_ms(rec):
    c = calls(rec)
    return None if c is None else sum(x["span_ns"] for x in c) / len(c) / 1e6


def hops_per_call(rec):
    c = calls(rec)
    if c is None:
        return None
    n = sum(x["hop_launches"] for x in c)
    return n / len(c) if n else None


def xla_ms_per_hop(rec):
    c = calls(rec)
    if c is None:
        return None
    n = sum(x["hop_launches"] for x in c)
    return sum(x["xla_ns"] for x in c) / n / 1e6 if n else None


def idle_share(rec):
    t = traced(rec)
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def hop_roofline(rec):
    from benchmarks.chip.peaks import peaks_for
    from benchmarks.chip.roofline import hop_share
    c = calls(rec)
    if c is None:
        return None
    bw = peaks_for(rec["device_kind"])["hbm_bytes_per_s"]
    return hop_share(rec["config"], c, bw)
