"""Compile the device search path for a described TPU v5e, no chip needed.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, vector reshapes Mosaic cannot lay out, programs larger than
HBM. Each test lowers a kernel (or the whole search) at Table-1 widths on
`ShapeDtypeStruct`s placed on a described `v5e:2x2` device and asserts the
Pallas kernel survived as a `tpu_custom_call`; the whole search also keeps
its loop-invariant LUT staging out of the loop body (checked against a
recorded compile where it was not).

The topology is described inside a fixture only: the TPU library may be
loaded by one process at a time, so describing it at import would break
multi-worker collection.
"""
import gzip
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.aisaq_indices import KILT_E5_22M, SIFT1B, SIFT1M
from repro.core.chunk_layout import layout_for
from repro.core.device_index import DeviceIndex, beam_search_device
from repro.kernels import ops
from repro.kernels.pq_lut import pq_lut
from repro.kernels.rerank import rerank

WIDTHS = {"sift1m": SIFT1M, "sift1b": SIFT1B, "kilt-e5": KILT_E5_22M}
HBM_BYTES = 16 * 10 ** 9          # one v5e chip
KERNEL = 'custom_call_target="tpu_custom_call"'
CHIP_CONFIGS = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" \
    / "configs"
# a served v5e search at nq 8, compiled before the LUT operands were pinned
# outside the loop
SUNK_LUT_HLO = CHIP_CONFIGS.parent / "tests" / "data" / "serve_q8.hlo.txt.gz"
LUT_SCOPE = "jit(_beam_search)/lut/"


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # the TPU library logs under /tmp unless given a directory
    os.environ.setdefault("TPU_LOG_DIR",
                          str(tmp_path_factory.mktemp("tpu_logs")))
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def loop_body_ops(hlo_text):
    """The instruction lines of every while body in compiled HLO text,
    with those of the computations each body calls (fusions, reducers,
    nested loops)."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split(" ", 2)
            cur = (head[1] if head[0] == "ENTRY" else head[0]).lstrip("%")
            comps[cur] = []
        elif cur is not None and re.match(r"\s+(ROOT )?%[\w.\-]+ = ", line):
            comps[cur].append(line)
    todo = [m for lines in comps.values() for line in lines
            for m in re.findall(r" while\(.*\bbody=%([\w.\-]+)", line)]
    seen, ops = set(todo), []
    while todo:
        comp = todo.pop()
        for line in comps.get(comp, ()):
            ops.append(line)
            for callee in re.findall(
                    r"\b(?:calls|to_apply|condition|body)=%([\w.\-]+)", line):
                if callee not in seen:
                    seen.add(callee)
                    todo.append(callee)
    return ops


def test_loop_body_ops_find_the_sunk_lut_tile():
    """Positive control for the assertion in `test_beam_search_compiles`:
    in a program compiled without the pin, the lane tile of the LUT runs
    in the loop body as a broadcast under the `lut` scope."""
    ops = loop_body_ops(gzip.decompress(SUNK_LUT_HLO.read_bytes()).decode())
    sunk = [line for line in ops if LUT_SCOPE + "tile" in line]
    assert any(" broadcast(" in line for line in sunk), sunk


def _rows(cfg):
    """A table one chip holds: the whole corpus or 1 M rows of it."""
    return min(cfg.n_vectors, 1_000_000)


@pytest.mark.parametrize("adc_dtype", ["f32", "int8"])
@pytest.mark.parametrize("name", list(WIDTHS))
def test_fused_hop_compiles(sds, name, adc_dtype):
    cfg = WIDTHS[name]
    lay = layout_for(cfg, "aisaq")
    nq, w = 32, cfg.beamwidth
    fn = jax.jit(lambda cw, f, lut, q: ops.fused_hop(
        cw, f, lut, q, layout=lay, metric=cfg.metric, backend="pallas",
        adc_dtype=adc_dtype))
    c = fn.lower(sds((_rows(cfg), lay.device_rows, 128), jnp.int32),
                 sds((nq, w), jnp.int32),
                 sds((nq, cfg.pq_m, cfg.pq_ks), jnp.float32),
                 sds((nq, cfg.dim), jnp.float32)).compile()
    assert KERNEL in c.as_text()


@pytest.mark.parametrize("name", list(WIDTHS))
def test_pq_lut_compiles(sds, name):
    cfg = WIDTHS[name]                     # dsub = 1, 4 and 8
    fn = jax.jit(lambda q, c: pq_lut(q, c, metric=cfg.metric))
    c = fn.lower(sds((1024, cfg.dim), jnp.float32),
                 sds((cfg.pq_m, cfg.pq_ks, cfg.dim // cfg.pq_m),
                     jnp.float32)).compile()
    assert KERNEL in c.as_text()


@pytest.mark.parametrize("name", list(WIDTHS))
def test_rerank_compiles(sds, name):
    cfg = WIDTHS[name]                     # one query x its 32 candidates
    fn = jax.jit(lambda q, c: rerank(q, c, metric=cfg.metric))
    c = fn.lower(sds((cfg.dim,), jnp.float32),
                 sds((32, cfg.dim), jnp.float32)).compile()
    assert KERNEL in c.as_text()


def _chip_search(name):
    """(rows on the chip, L, max_hops) of a chip benchmark configuration."""
    cfg = json.loads((CHIP_CONFIGS / f"{name}.json").read_text())
    s = cfg["assumed"]["search"]
    return cfg["n_vectors"], s["L"], s["max_hops"]


# the deployments the chip benchmark searches, as (widths, rows on the
# chip, L, max_hops): the whole of SIFT1M, and one of 44 shards of KILT E5
# 22M, each at the search settings of its configuration file
SEARCHES = {"sift1m": (SIFT1M, *_chip_search("aisaq-sift1m")),
            "kilt_1of44": (KILT_E5_22M,
                           *_chip_search("aisaq-kilt-e5-22m-1of44"))}


@pytest.mark.parametrize("name,nq", [
    ("sift1m", 32), ("sift1m", 1024), ("kilt_1of44", 32),
    ("kilt_1of44", 1024)], ids=["serve_q32", "serve_q1k",
                                "kilt_1of44-serve_q32",
                                "kilt_1of44-serve_q1k"])
def test_beam_search_compiles(sds, name, nq):
    cfg, rows, L, max_hops = SEARCHES[name]
    lay = layout_for(cfg, "aisaq")
    index = DeviceIndex(
        chunk_words=sds((rows, lay.device_rows, 128), jnp.int32),
        centroids=sds((cfg.pq_m, cfg.pq_ks, cfg.dim // cfg.pq_m),
                      jnp.float32),
        ep_ids=sds((1,), jnp.int32), ep_codes=sds((1, cfg.pq_m), jnp.int32))
    c = beam_search_device.lower(
        index, sds((nq, cfg.dim), jnp.float32), k=10, L=L, w=4,
        max_hops=max_hops, layout=lay, metric=cfg.metric,
        backend="pallas").compile()
    text = c.as_text()
    assert text.count(KERNEL) >= 2                  # LUT build + hop
    body = loop_body_ops(text)
    assert any("/while/body/hop/" in line for line in body)
    # the staged LUT is built once, before the loop, never per trip
    assert not [line for line in body if LUT_SCOPE in line]
    mem = c.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, used


def test_sharded_search_compiles(topo):
    """The (1, 4) mesh of the four-chip smoke phase, at sift1b widths."""
    from repro.core.sharded_search import input_sharding, sharded_search_fn
    cfg = SIFT1B
    lay = layout_for(cfg, "aisaq")
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(1, 4),
                             ("data", "model"))
    arr_sh, q_sh = input_sharding(mesh)
    n_s = 1 << 20
    shapes = type(arr_sh)(
        chunk_words=((4, n_s, lay.device_rows, 128), jnp.int32),
        centroids=((cfg.pq_m, cfg.pq_ks, cfg.dim // cfg.pq_m), jnp.float32),
        ep_ids=((4, 1), jnp.int32), ep_codes=((4, 1, cfg.pq_m), jnp.int32),
        offsets=((4,), jnp.int32))
    arrays = type(arr_sh)(*[jax.ShapeDtypeStruct(s, d, sharding=sh)
                            for (s, d), sh in zip(shapes, arr_sh)])
    search = sharded_search_fn(mesh, k=10, L=48, w=4, max_hops=128,
                               layout=lay, metric=cfg.metric,
                               backend="pallas")
    c = jax.jit(search).lower(
        arrays, jax.ShapeDtypeStruct((32, cfg.dim), jnp.float32,
                                     sharding=q_sh)).compile()
    text = c.as_text()
    assert text.count(KERNEL) >= 2 and "all-gather" in text
