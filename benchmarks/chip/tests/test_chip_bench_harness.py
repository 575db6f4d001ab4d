"""The chip benchmark's harness on the CPU, at a tiny size.

A configuration, two traffic mixes and a per-layer metric are added as
files in a copy of the benchmark, and found by name alone. The harness's
look for a chip is skipped; the rest of a run is driven through the
program's own search (its jnp backend), and the result line is checked.
Then the search is broken underneath in the ways a cell can break, and
``correct`` must come out false. Nothing here touches a TPU.
"""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness  # noqa: E402

TINY = {
    "name": "tiny", "source": "test", "deployment": "test",
    "n_vectors": 3000, "dim": 32, "data_dtype": "float32", "metric": "l2",
    "R": 16, "pq_m": 8, "pq_ks": 256, "reduced": [],
    "assumed": {
        "generator": {"index_seed": 11, "n_clusters": 8, "spread": 0.15,
                      "normalize": False, "long_edges": 4, "knn_block": 256, "query_noise": 0.05,
                      "pq_train_rows": 2048, "pq_iters": 4},
        "search": {"k": 10, "L": 24, "w": 4, "max_hops": 64,
                   "backend": "ref", "adc_dtype": "f32", "rerank": 0}},
    "correct": {"sample": 64, "wrong_ids_limit": 2},
}
TRAFFIC = {
    "tiny-bulk": {"kind": "bulk", "batch": 64, "k": 10, "query_pool": 512,
                  "trace_seconds": 0.5},
    "tiny-online": {"kind": "online", "rate_qps": 150, "k": 10,
                    "max_batch": 4, "max_wait_ms": 2.0, "query_pool": 512,
                    "trace_seconds": 0.5, "drain_seconds": 30},
}
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark with the tiny cells added as new files."""
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "benchmarks" / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY))
    for name, t in TRAFFIC.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(t))
    (bench / "metrics" / "answered.tiny.py").write_text(
        "def read(rec):\n    return len(rec['window'].qidx)\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "benchmarks/chip/configs/tiny.json",
                            "reduced": [], "why": "test"})
    cells = [f"tiny.{t}" for t in TRAFFIC]
    for name, t in zip(cells, TRAFFIC):
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": t, "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "recall_at_10":
            m["workloads"] = m["workloads"] + cells
    spec["end_to_end"][0]["workloads"].append("tiny.tiny-bulk")   # qps
    for m in spec["end_to_end"]:
        if m["name"] == "p50_ms":
            m["workloads"].append("tiny.tiny-online")
    for m in spec["per_layer"]:
        if m["name"] == "index_load_s":
            m["workloads"] = m["workloads"] + cells
        if m["name"] == "p99_ms.online":
            m["workloads"].append("tiny.tiny-online")
    spec["per_layer"].append({
        "name": "answered.tiny", "unit": "queries", "better": "higher",
        "source": "program_counter", "layer": "front end",
        "moves": "recall_at_10", "workloads": cells})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, bench


@pytest.fixture
def cpu(monkeypatch):
    """The harness's look for a TPU and its kernel check, skipped."""
    import jax
    monkeypatch.setattr(harness, "require_tpu", lambda chips: jax.devices())
    monkeypatch.setattr(harness, "check_kernels", lambda *a, **kw: None)


def run(tree, cell, trace=False, seconds=1.0, seed=2 ** 31 + 3):
    root, bench = tree
    c = harness.resolve(cell, root=root, bench=bench)
    line = harness.run_cell(c, seed, seconds, trace, t_start=0.0, root=root)
    return line, line["checks"]


@pytest.mark.parametrize("cell,e2e", [
    ("tiny.tiny-bulk", {"qps", "recall_at_10", "setup_s"}),
    ("tiny.tiny-online", {"p50_ms", "recall_at_10", "setup_s"}),
])
def test_cell_found_by_name_runs_and_is_correct(tree, cpu, cell, e2e):
    line, shown = run(tree, cell)
    assert list(line)[:5] == KEYS and list(line)[-1] == "checks"
    assert set(line["metrics"]) == e2e
    assert line["correct"], shown
    assert line["failed"] == 0 and line["attempted"] > 0
    assert shown["wrong_ids"][0] == 0 and shown["compared_ids"][0] > 0
    assert 0.5 < line["metrics"]["recall_at_10"]["value"] <= 1.0
    json.dumps(line, allow_nan=False)


def test_every_seed_gets_the_same_index_in_another_order(tree):
    """The seed draws the order of the work, never the work itself."""
    root, bench = tree
    c = harness.resolve("tiny.tiny-bulk", root=root, bench=bench)
    a = harness.build(c, 512, {})[0]
    b = harness.build(c, 512, {})[0]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    driver = harness.load_module(c.driver)
    orders = [harness.Run(c, s, 1.0, None, a, None, None, driver, {})
              .rng(1).permutation(8) for s in (2 ** 31 + 3, 2 ** 31 + 4)]
    assert sorted(orders[0]) == sorted(orders[1])
    assert list(orders[0]) != list(orders[1])


def test_traced_run_reports_only_per_layer_metrics(tree, cpu):
    line, _ = run(tree, "tiny.tiny-bulk", trace=True)
    # off a TPU there is no device plane: device metrics are left out, the
    # counter added as a file is read
    assert set(line["metrics"]) == {"index_load_s", "answered.tiny"}
    assert line["correct"]


def test_traced_online_run_keeps_the_profiler_out_of_its_window(tree, cpu):
    """The window is offered untraced and the traced segment after it: the
    window's requests are the same as an untraced run's, and its tail is
    read from them."""
    line, _ = run(tree, "tiny.tiny-online", trace=True)
    plain, _ = run(tree, "tiny.tiny-online")
    assert line["correct"] and line["attempted"] == plain["attempted"] == 150
    assert "p99_ms.online" in line["metrics"]
    assert line["generator_late_ms"]["p99"] < 1000.0


def _broken(kind):
    """A search fn factory whose answers are wrong in one way."""
    from repro.serving import engine
    real = engine.make_device_search_fn

    def factory(*a, **kw):
        fn = real(*a, **kw)
        last = {}

        def search(queries, k):
            ids = np.array(fn(queries, k))
            if kind == "altered":          # one answer id altered
                ids[:, -1] = (ids[:, -1] + 1) % 3000
            elif kind == "half_batch":     # second half never searched
                h = len(ids) // 2
                ids[h:] = ids[:len(ids) - h]
            elif kind == "unchanged":      # the state handed back as it was
                ids = last.get(len(ids), ids[::-1].copy())
                last[len(ids)] = np.array(fn(queries, k))
            return ids
        return search
    return factory


@pytest.mark.parametrize("kind", ["altered", "half_batch", "unchanged"])
def test_broken_search_is_not_correct(tree, cpu, monkeypatch, kind):
    from repro.serving import engine
    monkeypatch.setattr(engine, "make_device_search_fn", _broken(kind))
    line, shown = run(tree, "tiny.tiny-bulk", seconds=0.5)
    assert not line["correct"], shown
    assert shown["wrong_ids"][0] > shown["wrong_ids"][1]


@pytest.mark.parametrize("cell", ["tiny.tiny-bulk", "tiny.tiny-online"])
def test_control_through_the_harness_is_not_correct(tree, cpu, cell):
    """The bfloat16 reference in the program's place, driven and judged by
    the harness exactly as a run is, comes out not correct."""
    import jax
    from benchmarks.chip import control
    root, bench = tree
    c = harness.resolve(cell, root=root, bench=bench)
    line = control.control_line(c, 2 ** 31 + 5, 0.3, jax.devices())
    shown = line["checks"]
    assert not line["correct"], shown
    assert shown["wrong_ids"][0] > shown["wrong_ids"][1]
    assert shown["unanswered"][0] == 0


def test_main_refuses_a_host_without_tpu(capsys):
    rc = harness.main(["--workload", "sift1m.bulk-q1k", "--seed", "5",
                       "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_unknown_names_raise(tree):
    root, bench = tree
    with pytest.raises(KeyError):
        harness.resolve("no.such-cell", root=root, bench=bench)


def test_configuration_without_a_measured_limit_is_refused(tree, tmp_path):
    root, bench = tree
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "kilt", "source": "test", "reduced": [], "why": "test",
        "file": "benchmarks/chip/configs/aisaq-kilt-e5-1of44.json"})
    spec["workloads"].append({"name": "kilt.tiny-online", "config": "kilt",
                              "traffic": "tiny-online", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "benchmarks").symlink_to(root / "benchmarks")
    with pytest.raises(ValueError, match="no measured wrong_ids limit"):
        harness.resolve("kilt.tiny-online", root=tmp_path, bench=bench)
