"""Multi-device behaviour on 8 virtual CPU devices (subprocess-isolated so
the main test session keeps exactly one device)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(script: str, devices: int = 8, timeout: int = 520):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_search_recall():
    run_py("""
import numpy as np, jax, jax.numpy as jnp
from repro.data.vectors import make_clustered, make_queries
from repro.core import pq
from repro.core.vamana import build_sharded
from repro.core.chunk_layout import ChunkLayout
from repro.core.sharded_search import stack_shards, sharded_search_fn, input_sharding
from repro.core.index_io import recall_at
base = make_clustered(1600, 32, seed=0); q = make_queries(8, base)
gt = pq.groundtruth(q, base, 10)
cb = pq.train_codebooks(jax.random.PRNGKey(0), base, m=8, iters=6)
cents = np.asarray(cb.centroids); codes = np.asarray(pq.encode(cb, base))
lay = ChunkLayout('aisaq', 32, 'float32', 16, 8)
shards = build_sharded(base, 4, R=16, L=32, seed=0)
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((2, 4), ('data', 'model'))
arrays = stack_shards(shards, cents, codes, lay, mesh)
search = sharded_search_fn(mesh, k=10, L=48, w=4, max_hops=64, layout=lay, metric='l2', backend='ref')
_, qsh = input_sharding(mesh)
ids, dd = jax.jit(search)(arrays, jax.device_put(jnp.asarray(q), qsh))
r1 = recall_at(np.asarray(ids), gt, 1); r10 = recall_at(np.asarray(ids), gt, 10)
assert r1 >= 0.9 and r10 >= 0.85, (r1, r10)
print('sharded recall OK', r1, r10)
""")


def test_dp_training_matches_single_device():
    """Loss trajectory on a (2,4) mesh == single-device trajectory."""
    out = run_py("""
import numpy as np, jax
from repro.launch.train import train_loop
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((2, 4))
h = train_loop('qwen3-1.7b', 'train_4k', steps=6, mesh=mesh, verbose=False)
print('LOSSES', ','.join(f'{l:.5f}' for l in h['losses']))
""")
    losses_dp = [float(x) for x in
                 out.split("LOSSES ")[1].strip().split(",")]
    out1 = run_py("""
import numpy as np
from repro.launch.train import train_loop
h = train_loop('qwen3-1.7b', 'train_4k', steps=6, verbose=False)
print('LOSSES', ','.join(f'{l:.5f}' for l in h['losses']))
""", devices=1)
    losses_1 = [float(x) for x in
                out1.split("LOSSES ")[1].strip().split(",")]
    assert abs(losses_dp[-1] - losses_1[-1]) < 0.05, (losses_dp, losses_1)


def test_elastic_checkpoint_reshard():
    """Save sharded state on a (2,4) mesh, restore onto (4,2) AND onto a
    single device — topology-agnostic checkpoints (elastic scaling)."""
    run_py("""
import jax, numpy as np, tempfile
from repro.launch.train import train_loop, build_trainer
from repro.launch.mesh import make_test_mesh
from repro.checkpoint.ckpt import restore, latest_step
d = tempfile.mkdtemp()
mesh = make_test_mesh((2, 4))
h = train_loop('qwen3-1.7b', 'train_4k', steps=4, mesh=mesh, ckpt_dir=d, ckpt_every=2, verbose=False)
mesh2 = make_test_mesh((4, 2))
arch, state_init, jit_step, data_gen, sh2 = build_trainer('qwen3-1.7b', 'train_4k', mesh=mesh2)
st = restore(d, state_init(), shardings=sh2)
import jax.numpy as jnp
batch = {k: jnp.asarray(v) for k, v in data_gen(4).items()}
st2, m = jit_step(st, batch)
assert np.isfinite(float(m['loss']))
print('resharded restore OK, loss', float(m['loss']))
""")


def test_pipeline_parallel_matches_sequential():
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import make_pp_mesh, pipeline_forward
S, M, mb, d = 4, 8, 2, 16
mesh = make_pp_mesh(S, 2)
rng = np.random.default_rng(0)
W = jnp.asarray(rng.normal(size=(8, d, d)).astype(np.float32)) * 0.3  # 8 layers
x = jnp.asarray(rng.normal(size=(M * mb, d)).astype(np.float32))
def stage_fn(params, xb):
    def body(h, w):
        return jnp.tanh(h @ w), None
    h, _ = jax.lax.scan(body, xb, params)
    return h
pipe = pipeline_forward(mesh, stage_fn, M)
xp = x.reshape(M, mb, d)
out = jax.jit(pipe)(W.reshape(S, 2, d, d), xp)
ref = stage_fn(W, x).reshape(M, mb, d)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
print('pipeline OK')
""")


def test_compressed_grad_allreduce():
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compressed_psum
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((8,), ('data',))
rng = np.random.default_rng(0)
g = jnp.asarray(rng.normal(size=(8, 4096)).astype(np.float32))
def local(gs):
    return compressed_psum({'g': gs[0]}, 'data')['g']
out = jax.shard_map(local, mesh=mesh, in_specs=(P('data', None),), out_specs=P(None), check_vma=False)(g)
ref = g.mean(0)
rel = float(jnp.abs(out - ref).max() / (jnp.abs(ref).max() + 1e-9))
assert rel < 0.02, rel      # int8 grade
print('compressed psum OK rel', rel)
""")


def test_cp_attention_matches_reference():
    """Context-parallel attention (§Perf cp-attn): loss + grads match the
    single-device reference bit-near-exactly."""
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import LMConfig
from repro.models import transformer as T
from repro.distributed.act_sharding import set_policy
from repro.launch.mesh import make_test_mesh
cfg = LMConfig(name='t', n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
               d_ff=128, vocab_size=512, attention='sliding', window=256, dtype='float32')
p = T.init_lm(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 1024), 0, 512)
batch = {'tokens': toks, 'labels': jnp.roll(toks, -1, 1)}
set_policy(None)
l_ref = jax.jit(lambda p, b: T.lm_loss(p, b, cfg)[0])(p, batch)
g_ref = jax.jit(jax.grad(lambda p: T.lm_loss(p, batch, cfg)[0]))(p)
set_policy(make_test_mesh((2, 4)), cp_attention=True)
l_cp = jax.jit(lambda p, b: T.lm_loss(p, b, cfg)[0])(p, batch)
g_cp = jax.jit(jax.grad(lambda p: T.lm_loss(p, batch, cfg)[0]))(p)
set_policy(None)
m = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.abs(a-b).max()/(jnp.abs(a).max()+1e-9)), g_ref, g_cp)))
assert abs(float(l_ref) - float(l_cp)) < 1e-4 and m < 5e-3, (float(l_ref), float(l_cp), m)
print('cp attention OK', m)
""")


def test_gnn_sharded_matches_reference():
    """Partitioned GNN aggregation (§Perf gnn-part) == replicated baseline."""
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import GNNConfig
from repro.models import gnn as G
from repro.models.gnn_sharded import partition_edges, sharded_full_loss_fn
from repro.launch.mesh import make_test_mesh
from repro.data.pipeline import make_graph
cfg = GNNConfig(name='t', n_layers=2, d_hidden=32, n_classes=7)
g = make_graph(200, 6, 24, 7, seed=0)
p = G.init_gnn(jax.random.PRNGKey(0), cfg, d_feat=24)
batch = {k: jnp.asarray(v) for k, v in g.items()}
l_ref, _ = jax.jit(lambda p, b: G.gnn_full_loss(p, b, cfg))(p, batch)
mesh = make_test_mesh((2, 4))
pe, _ = partition_edges(g['edges'], 200, 8)
batch2 = dict(batch); batch2['edges'] = jnp.asarray(pe)
loss_fn = sharded_full_loss_fn(mesh, cfg, 200, wire_dtype=jnp.float32)
l_sh, _ = jax.jit(loss_fn)(p, batch2)
g_ref = jax.jit(jax.grad(lambda p: G.gnn_full_loss(p, batch, cfg)[0]))(p)
g_sh = jax.jit(jax.grad(lambda p: loss_fn(p, batch2)[0]))(p)
m = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.abs(a-b).max()/(jnp.abs(a).max()+1e-9)), g_ref, g_sh)))
assert abs(float(l_ref) - float(l_sh)) < 1e-4 and m < 1e-3
print('sharded gnn OK', m)
""")


def test_moe_ep_matches_global_dispatch():
    """shard_map EP MoE (§Perf moe-ep) == GSPMD global dispatch."""
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import MoEConfig
from repro.models.moe import init_moe, moe_apply, moe_apply_ep
from repro.distributed.act_sharding import set_policy
from repro.launch.mesh import make_test_mesh
mc = MoEConfig(n_experts=8, top_k=2, d_expert=32, capacity_factor=16.0,
               n_shared_experts=1, d_shared=32)
p = init_moe(jax.random.PRNGKey(0), 48, mc, jnp.float32)
x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 48)), jnp.float32)
set_policy(None)
out_ref, _ = jax.jit(lambda p, x: moe_apply(p, x, mc))(p, x)
g_ref = jax.jit(jax.grad(lambda p: (moe_apply(p, x, mc)[0]**2).sum()))(p)
set_policy(make_test_mesh((2, 4)))
out_ep, _ = jax.jit(lambda p, x: moe_apply_ep(p, x, mc))(p, x)
g_ep = jax.jit(jax.grad(lambda p: (moe_apply_ep(p, x, mc)[0]**2).sum()))(p)
set_policy(None)
err = float(jnp.abs(out_ref - out_ep).max()/(jnp.abs(out_ref).max()+1e-9))
gerr = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.abs(a-b).max()/(jnp.abs(a).max()+1e-9)), g_ref, g_ep)))
assert err < 1e-5 and gerr < 1e-4, (err, gerr)
print('moe ep OK', err, gerr)
""")


def test_ann_cell_runs_small_mesh():
    """Execute (not just compile) the dry-run ANN search program shape on
    8 devices with a real small index."""
    run_py("""
import numpy as np, jax, jax.numpy as jnp
from repro.data.vectors import make_clustered, make_queries
from repro.core import pq
from repro.core.vamana import build_sharded
from repro.core.chunk_layout import ChunkLayout
from repro.core.sharded_search import stack_shards, sharded_search_fn, input_sharding
from repro.core.index_io import recall_at
# mode B: shards over EVERY axis, queries replicated + chunked
base = make_clustered(1600, 32, seed=0); q = make_queries(16, base)
gt = pq.groundtruth(q, base, 10)
cb = pq.train_codebooks(jax.random.PRNGKey(0), base, m=8, iters=6)
cents = np.asarray(cb.centroids); codes = np.asarray(pq.encode(cb, base))
lay = ChunkLayout('aisaq', 32, 'float32', 16, 8)
shards = build_sharded(base, 8, R=16, L=32, seed=0)
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((2, 4), ('data', 'model'))
arrays = stack_shards(shards, cents, codes, lay, mesh,
                      shard_axes=('data', 'model'))
search = sharded_search_fn(mesh, k=10, L=48, w=4, max_hops=64, layout=lay,
                           metric='l2', backend='ref', query_axes=(),
                           shard_axes=('data', 'model'), query_chunk=8)
ids, dd = jax.jit(search)(arrays, jnp.asarray(q))
r1 = recall_at(np.asarray(ids), gt, 1)
assert r1 >= 0.85, r1
print('mode-B sharded search OK', r1)
""")
