"""Trace reduction, the roofline byte count and the peak table.

``reduce_events`` is fed hand-made events whose answers are known; the
recorded trace of a small search on a TPU v5e (``data/``) is read through
the whole path, ``.xplane.pb`` to metrics.
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import roofline, trace_reduce  # noqa: E402
from benchmarks.chip.peaks import peaks_for  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "q8.xplane.pb"
SIFT = json.loads((ROOT / "benchmarks" / "chip" / "configs" /
                   "aisaq-sift1m.json").read_text())


def test_reduce_hand_made_events():
    calls = [(100, 200, 32), (300, 420, 16)]
    ops = {"/device:TPU:0": [
        ("%while.1 = (s32[8]) while(...)", 120, 190),   # encloses its body
        ("pq_lut.1", 110, 120), ("%hop.4 = (f32[8]) custom-call(...)", 120,
                                 150), ("top-k.2", 150, 160),
        ("hop.4", 160, 190),
        ("pq_lut.1", 310, 320), ("hop.4", 330, 400), ("fusion.9", 400, 410),
        ("copy.1", 500, 510)]}        # after the window: not counted
    r = trace_reduce.reduce_events(calls, ops)
    assert r["window_s"] == pytest.approx(320e-9)
    assert r["busy_s"] == pytest.approx(170e-9)
    c0, c1 = r["calls"]
    assert (c0["nq"], c0["hop_launches"], c0["span_ns"]) == (32, 2, 100)
    assert c0["kernel_ns"] == {"pq_lut": 10, "hop": 60}
    assert c0["xla_ns"] == 10         # top-k; while has no time of its own
    assert (c1["hop_launches"], c1["xla_ns"]) == (1, 10)
    ops_ = dict(r["breakdown"]["device_ops"])
    assert ops_["hop"] == pytest.approx(130e-9)
    assert ops_["while"] == pytest.approx(0)
    assert "copy" not in ops_
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["between calls", pytest.approx(120e-9)]
    assert sum(g for _, g in gaps) == pytest.approx(150e-9)
    assert ["in search_call", pytest.approx(10e-9)] in gaps


def test_nothing_to_read_gives_none():
    assert trace_reduce.reduce_events([], {"/device:TPU:0": []}) is None
    assert trace_reduce.reduce_events([(0, 1, 1)], {}) is None


def test_hop_bytes_at_table1_widths():
    assert roofline.chunk_bytes(SIFT) == 7908          # 512 + 228 + 7168
    kilt = json.loads((ROOT / "benchmarks" / "chip" / "configs" /
                       "aisaq-kilt-e5-1of44.json").read_text())
    assert roofline.chunk_bytes(kilt) == 13208         # 4096 + 280 + 8832
    calls = [{"nq": 2, "hop_launches": 3, "kernel_ns": {"hop": 1e6}}]
    share = roofline.hop_share(SIFT, calls, 819e9)
    need = 2 * 3 * 4 * (7908 + 4 + 8 * 56)
    assert share == pytest.approx(100 * need / 819e9 / 1e-3)


def test_unknown_device_raises():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def test_recorded_tpu_trace():
    """A v5e trace of three 8-query searches of a 20,000-row index."""
    import jax
    pdata = jax.profiler.ProfileData.from_file(str(RECORDED))
    r = trace_reduce.reduce_events(*trace_reduce.read_events(pdata))
    assert [c["nq"] for c in r["calls"]] == [8, 8, 8]
    for c in r["calls"]:
        assert c["hop_launches"] > 5 and c["xla_ns"] > 0
    assert sum(c["kernel_ns"].get("pq_lut", 0) for c in r["calls"]) > 0
    assert 0 < r["busy_s"] < r["window_s"]
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert "hop" in names
    share = roofline.hop_share(SIFT, r["calls"], 819e9)
    assert 0 < share < 100
