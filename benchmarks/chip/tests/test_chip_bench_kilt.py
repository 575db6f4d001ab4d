"""The KILT-E5 1/44 shard configuration on the CPU, at a small size.

The configuration file's own recipe and search settings, cut to 4,000 rows
in 8 clusters: the program's device search (jnp backend, and the Pallas
kernels in interpret mode) gives the plain reference's answers under MIPS
at d=1024 and R=69. The harness finds both of the configuration's cells by
name in a copy of the benchmark, with the metrics of the SIFT cell of the
same traffic kind; the online cell offers the online mix at its own rate.
The file keeps the generator values of the unchecked draft it grew from,
so its index is the one that draft described.
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

from benchmarks.chip import harness, reference, roofline  # noqa: E402
from benchmarks.chip.data import index_gen  # noqa: E402

CONFIGS = ROOT / "benchmarks" / "chip" / "configs"
NAME = "aisaq-kilt-e5-22m-1of44"
DRAFT = "aisaq-kilt-e5-1of44"
N, NQ, SEED = 4000, 8, 2 ** 31 + 29
CELLS = {"kilt-e5.online-q32": "sift1m.online-q32",
         "kilt-e5.bulk-q1k": "sift1m.bulk-q1k"}


def config(name=NAME) -> dict:
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def kilt():
    cfg = config()
    cfg["n_vectors"] = N
    gen = dict(cfg["assumed"]["generator"], n_clusters=8, pq_train_rows=2048)
    cfg["assumed"] = dict(cfg["assumed"], generator=gen)
    ix = index_gen.make_index(SEED, cfg, NQ)
    s = cfg["assumed"]["search"]
    ref, _, _ = reference.beam_search(
        ix.base, ix.graph, ix.codes, ix.centroids, ix.queries, k=s["k"],
        L=s["L"], w=s["w"], max_hops=s["max_hops"], metric=cfg["metric"])
    return cfg, ix, ref


@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
def test_device_search_gives_the_reference_answers(kilt, backend):
    import jax.numpy as jnp
    from repro.core.device_index import beam_search_device, from_arrays
    cfg, ix, ref = kilt
    s = cfg["assumed"]["search"]
    idx, layout = from_arrays(ix.base, ix.graph, ix.centroids, ix.codes)
    assert layout.device_rows == 32          # 13,208 B of payload a chunk
    got, _, _ = beam_search_device(
        idx, jnp.asarray(ix.queries), k=s["k"], L=s["L"], w=s["w"],
        max_hops=s["max_hops"], layout=layout, metric="mips",
        backend=backend, adc_dtype=s["adc_dtype"])
    assert reference.wrong_ids(ix.base, ix.queries, np.asarray(got), ref,
                               "mips", s["k"]) == 0


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark as it stands: the cells found by name."""
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "benchmarks" / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root, bench


@pytest.mark.parametrize("cell", list(CELLS))
def test_harness_resolves_the_cell(tree, cell):
    root, bench = tree
    c = harness.resolve(cell, root=root, bench=bench)
    sift = harness.resolve(CELLS[cell], root=root, bench=bench)
    assert c.chips == 1 and c.config["name"] == NAME
    assert c.config["correct"]["wrong_ids_limit"] >= 0
    assert c.traffic["kind"] == sift.traffic["kind"]
    assert c.driver == sift.driver
    names = {m["name"] for m in c.end_to_end + c.per_layer}
    assert names == {m["name"] for m in sift.end_to_end + sift.per_layer}
    assert all(p.is_file() for p in c.metric_files.values())


def test_chunk_bytes_at_these_widths():
    # vector 4,096 B, degree and ids 280 B, inline codes 8,832 B
    assert roofline.chunk_bytes(config()) == 13208


def test_generator_values_are_the_drafts():
    new, draft = config(), config(DRAFT)
    assert new["assumed"]["generator"] == draft["assumed"]["generator"]
    for key in ("n_vectors", "published_n_vectors", "shards", "dim",
                "data_dtype", "metric", "R", "pq_m", "pq_ks"):
        assert new[key] == draft[key], key
    assert new["reduced"] == ["n_vectors"]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in spec["configs"]}[NAME]
    assert entry["reduced"] == new["reduced"]
    assert entry["file"] == f"benchmarks/chip/configs/{NAME}.json"


def test_online_traffic_is_the_online_mix_at_its_own_rate():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = {w["name"]: w for w in spec["workloads"]}[
        "kilt-e5.online-q32"]["traffic"]
    traffic = ROOT / "benchmarks" / "chip" / "traffic"
    t = json.loads((traffic / f"{name}.json").read_text())
    base = json.loads((traffic / "online-q32-r960.json").read_text())
    assert name == f"online-q32-r{t['rate_qps']}"
    assert f"{t['rate_qps']:,}/s" in t["about"]
    same = set(base) - {"rate_qps", "about"}
    assert set(t) == set(base)
    assert {k: t[k] for k in same} == {k: base[k] for k in same}
