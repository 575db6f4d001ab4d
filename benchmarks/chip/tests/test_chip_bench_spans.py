"""The reduction of the program's spans and scopes (``span_reduce``).

Hand-made compiled text and events whose answers are known; the cell of
a record found by name; and the recorded trace of a few ``ServingEngine``
batches on a TPU v5e (``data/``), read through the per-layer readers.
"""
import gzip
import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness, span_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
BENCH = ROOT / "benchmarks" / "chip"

HLO = """HloModule jit__beam_search, is_scheduled=true

%fused_computation.1 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %add.1 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(_beam_search)/while/body/visited/add"}
}

%cond (c: (s32[8])) -> pred[] {
  %c = (s32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true), metadata={op_name="jit(_beam_search)/while/cond/lt"}
}

%body (p: (s32[8])) -> (s32[8]) {
  %p = (s32[8]{0}) parameter(0)
  %get-tuple-element.1 = s32[8]{0} get-tuple-element(%p), index=0
  %sort.1 = s32[8]{0} sort(%get-tuple-element.1), dimensions={0}, metadata={op_name="jit(_beam_search)/while/body/frontier/top_k"}
  %copy.2 = s32[8]{0} copy(%get-tuple-element.1)
  %fusion.1 = s32[8]{0} fusion(%sort.1), kind=kLoop, calls=%fused_computation.1
  %copy.1 = s32[8]{0} copy(%fusion.1)
  %hop.4 = s32[8]{0} custom-call(%copy.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_beam_search)/while/body/hop/jit(hop)/pallas_call"}
  %reshape.2 = s32[8]{0} reshape(%hop.4), metadata={op_name="jit(_beam_search)/while/body/visited/reshape"}
  %sort.2 = s32[8]{0} sort(%reshape.2), dimensions={0}
  %iota.1 = s32[8]{0} iota(), iota_dimension=0
  %reshape.3 = s32[8]{0} reshape(%sort.2), metadata={op_name="jit(_beam_search)/while/body/visited/scatter"}
  ROOT %tuple.1 = (s32[8]{0}) tuple(%reshape.3, %copy.2, %iota.1)
}

ENTRY %main.1 (q: s32[8]) -> s32[8] {
  %q = s32[8]{0} parameter(0)
  %pq_lut.1 = s32[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(_beam_search)/lut/jit(pq_lut)/pallas_call"}
  %tuple.0 = (s32[8]{0}) tuple(%pq_lut.1)
  %while.1 = (s32[8]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(_beam_search)/while"}
  ROOT %get-tuple-element.9 = s32[8]{0} get-tuple-element(%while.1), index=0
}
"""


def test_scope_map_of_compiled_text():
    m = span_reduce.scope_map(HLO)
    scope = {k: v[0] for k, v in m.items()}
    assert scope["sort.1"] == "frontier"          # its own op_name
    assert scope["fusion.1"] == "visited"         # its fused instructions'
    assert scope["copy.1"] == "visited"           # its operand's
    assert scope["sort.2"] == "visited"
    assert scope["iota.1"] == "visited"           # between two visited ops
    assert scope["copy.2"] == "none"              # between frontier, visited
    assert scope["hop.4"] == "hop" and scope["pq_lut.1"] == "lut"
    assert scope["lt.1"] == scope["while.1"] == "none"
    assert m["hop.4"][1:3] == (True, True)        # in the loop, Pallas
    assert m["sort.2"][1:3] == (True, False)
    assert m["lt.1"][1] and not m["pq_lut.1"][1]
    assert m["sort.1"][3] == ("sort.1", "s32[8]{0}", "sort")


def test_idle_split_adds_up_to_the_idle_time():
    calls = [(100, 300, 4)]
    ops = {"/device:TPU:0": [("a.1", 120, 150), ("b.1", 200, 220)]}
    spans = [("engine.collect", 50, 110), ("search.call", 110, 260),
             ("search.stage", 110, 118), ("search.dispatch", 118, 125),
             ("search.fetch", 125, 250), ("engine.fanout", 260, 280)]
    r = span_reduce.idle_split(calls, ops, spans)
    assert r["idle_ns"] == {
        "search.stage": 8, "search.dispatch": 2, "search.fetch": 80,
        "engine.fanout": 20, "engine.collect": 10, "search.call": 10,
        "none": 20}
    assert r["idle_total_ns"] == 150 == sum(r["idle_ns"].values())
    assert r["calls"] == 1
    assert span_reduce.idle_split([], ops, spans) is None
    assert span_reduce.idle_split(calls, {}, spans) is None


def test_scope_times_count_only_calls_of_a_known_program():
    smap = span_reduce.scope_map(HLO)
    ops = {"/device:TPU:0": [
        ("%pq_lut.1 = s32[8]{0} custom-call(s32[8]{0} %q)", 100, 110),
        ("%while.1 = (s32[8]{0}) while(...)", 110, 200),
        ("sort.1", 110, 120), ("fusion.1", 120, 125), ("copy.2", 125, 130),
        ("hop.4", 130, 160), ("sort.2", 160, 170), ("sort.1", 170, 175),
        ("hop.4", 175, 185),
        # a call of another program (nq 4), and one whose op differs
        ("sort.1", 310, 320),
        ("%sort.1 = s32[16]{0} sort(...)", 410, 420)]}
    calls = [(90, 210, 8), (300, 330, 4), (400, 430, 8)]
    r = span_reduce.scope_times(calls, ops,
                                lambda nq: smap if nq == 8 else None)
    assert r["mapped_calls"] == 1 and r["trips"] == 2
    # the while op's own time (185 to 200) lies in no scope, and outside
    # its body
    assert r["scope_ns"] == {"lut": 10, "none": 20, "frontier": 15,
                             "visited": 15, "hop": 40}
    assert r["loop_ns"] == {"scoped": 30, "unscoped": 5}
    assert r["unscoped_ns"] == {"while": 15, "copy": 5}
    ph = span_reduce.phases(None, r)
    assert ph["loop_unscoped_share"] == pytest.approx(100 * 5 / 35)
    assert span_reduce.scope_times(calls, ops, lambda nq: None) is None


def test_a_record_finds_its_cell_trace_directory():
    cfg = json.loads((BENCH / "configs" / "aisaq-sift1m.json").read_text())
    for traffic, cell in (("online-q32-r1200", "sift1m.online-q32"),
                          ("bulk-q1k", "sift1m.bulk-q1k")):
        t = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
        got = span_reduce.trace_dir({"config": cfg, "traffic": t},
                                    str(BENCH / "metrics" / "x.py"))
        assert got == ROOT / ".bench_cache" / "trace" / cell
    assert span_reduce.trace_dir({"config": {}, "traffic": {}},
                                 str(BENCH / "metrics" / "x.py")) is None


RECORDED = DATA / "serve_q8.xplane.pb"
COMPILED = DATA / "serve_q8.hlo.txt.gz"


def test_recorded_serving_trace(tmp_path, monkeypatch):
    """A v5e trace of three ``ServingEngine`` batches of 8 queries over a
    20,000-row sift-width index, the profiler mirror installed, read by the
    per-layer readers; the program's compiled text is recorded with it."""
    from benchmarks.chip import trace_reduce
    smap = span_reduce.scope_map(gzip.decompress(COMPILED.read_bytes())
                                 .decode())
    d = tmp_path / "trace"
    d.mkdir()
    shutil.copy(RECORDED, d / "serve_q8.xplane.pb")
    monkeypatch.setattr(span_reduce, "_CACHE", {})
    monkeypatch.setattr(span_reduce, "trace_dir", lambda rec, f: d)
    monkeypatch.setattr(span_reduce, "served_programs",
                        lambda rec: lambda nq: smap if nq == 8 else None)
    rec = {"window": SimpleNamespace(trace={"calls": []})}
    read = {name: harness.load_module(BENCH / "metrics" / f"{name}.py")
            .read(rec) for name in ("idle_collect_ms.online",
                                    "idle_host_ms.online",
                                    "visited_ms_per_hop.online")}
    assert all(0 < v < 50 for v in read.values()), read
    r = span_reduce.reduce_run(rec, str(BENCH / "metrics" / "x.py"))
    assert set(r["spans_seen"]) == set(span_reduce.SPANS)
    idle = r["idle"]
    assert idle["calls"] == 3
    assert sum(idle["idle_ns"].values()) == pytest.approx(
        idle["idle_total_ns"], rel=1e-9)
    import jax
    host_calls, device_ops = trace_reduce.read_events(
        jax.profiler.ProfileData.from_file(str(RECORDED)))
    base = trace_reduce.reduce_events(host_calls, device_ops)
    assert [c["nq"] for c in base["calls"]] == [8, 8, 8]
    assert idle["idle_total_ns"] == pytest.approx(
        base["window_s"] * 1e9 - base["busy_s"] * 1e9, rel=1e-6)
    s = r["scopes"]
    assert s["mapped_calls"] == 3
    assert s["trips"] == sum(c["hop_launches"] for c in base["calls"])
    assert set(s["scope_ns"]) >= set(span_reduce.SCOPES)
    assert r["phases"]["loop_unscoped_share"] < 20


def test_readers_read_nothing_from_a_program_without_spans(tmp_path,
                                                           monkeypatch):
    """The recorded trace of a program with neither spans nor named scopes
    (``q8.xplane.pb``), whose search fn has no ``lower``: every reader of
    this module gives None, and raises nothing."""
    d = tmp_path / "trace"
    d.mkdir()
    shutil.copy(DATA / "q8.xplane.pb", d / "q8.xplane.pb")
    monkeypatch.setattr(span_reduce, "_CACHE", {})
    monkeypatch.setattr(span_reduce, "trace_dir", lambda rec, f: d)
    monkeypatch.setattr(span_reduce, "served_programs", lambda rec: None)
    rec = {"window": SimpleNamespace(trace={"calls": []})}
    for name in ("idle_collect_ms.online", "idle_host_ms.online",
                 "visited_ms_per_hop.online", "visited_ms_per_hop.bulk"):
        assert harness.load_module(BENCH / "metrics" / f"{name}.py") \
            .read(rec) is None, name
    r = span_reduce.reduce_run(rec, str(BENCH / "metrics" / "x.py"))
    assert r["spans_seen"] == [] and r["idle"] is None \
        and r["scopes"] is None


# The tiny cells of the harness's own test, run end to end on the CPU.
_cells = harness.load_module(Path(__file__).resolve().parent
                             / "test_chip_bench_harness.py")
tree, cpu = _cells.tree, _cells.cpu
SPAN_METRICS = {"idle_collect_ms.online", "idle_host_ms.online",
                "visited_ms_per_hop.bulk"}


@pytest.fixture
def spans_tree(tree, tmp_path):
    """The tiny bulk cell with the device path's span and scope metrics."""
    root, _ = tree
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        if m["name"] in SPAN_METRICS:
            m["workloads"] = m["workloads"] + ["tiny.tiny-bulk"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "benchmarks").symlink_to(root / "benchmarks")
    return tmp_path, tmp_path / "benchmarks" / "chip"


def test_span_metrics_fall_silent_on_a_program_without_them(
        spans_tree, cpu, monkeypatch):
    """Off a TPU the trace has no device plane, so the span and scope
    metrics read nothing, and they raise nothing; a search fn without
    spans or ``lower`` (an older program) gives the same line."""
    from repro.obs import trace
    from repro.serving import engine
    line, _ = _cells.run(spans_tree, "tiny.tiny-bulk", trace=True)
    got = set(line["metrics"])
    assert "index_load_s" in got and not SPAN_METRICS & got
    real = engine.make_device_search_fn

    def plain(*a, **kw):
        fn = real(*a, **kw)
        return lambda queries, k: fn(queries, k)
    monkeypatch.setattr(trace, "_MIRROR", None)
    monkeypatch.setattr(engine, "make_device_search_fn", plain)
    old, _ = _cells.run(spans_tree, "tiny.tiny-bulk", trace=True)
    assert set(old["metrics"]) == got
    assert old["correct"] and list(old) == list(line)
