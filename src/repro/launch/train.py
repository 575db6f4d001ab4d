"""Training launcher: mesh setup, sharded state init, checkpoint/restart,
heartbeats, deterministic data resume.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --shape train_4k --steps 100 --ckpt-dir /tmp/ckpt [--scale tiny]

On real clusters this binary runs per-host under the cluster manager; here
it also backs examples/train_lm.py and the fault-tolerance tests.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.ckpt import AsyncCheckpointer, latest_step, restore
from repro.configs import get_arch
from repro.distributed import sharding as SH
from repro.distributed.act_sharding import set_policy
from repro.distributed.fault_tolerance import Heartbeat, WorkerFailure
from repro.distributed.train_step import (TrainState, default_optimizer,
                                          make_train_step)


def tiny_lm(cfg):
    return cfg.scaled(n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
                      head_dim=32, d_ff=256, vocab_size=2048,
                      window=min(cfg.window, 64) if cfg.window else 0,
                      moe=None, dtype="float32")


def build_trainer(arch_id: str, shape_name: str, *, mesh=None,
                  scale: str = "tiny", microbatches: int = 1,
                  lr: float = 3e-3, steps: int = 100):
    """Returns (state_init_fn, jit_step, data_gen, shardings)."""
    arch = get_arch(arch_id)
    shape = arch.shape(shape_name)
    if scale == "tiny":
        if arch.family == "lm":
            arch = dataclasses.replace(arch, model=tiny_lm(arch.model))
            shape = dataclasses.replace(shape, seq_len=128,
                                        global_batch=max(4, mesh.shape.get(
                                            "data", 1) if mesh else 4))
        elif arch.family == "gnn":
            arch = dataclasses.replace(
                arch, model=arch.model.scaled(d_hidden=32, n_classes=8))
            shape = dataclasses.replace(shape, n_nodes=256, n_edges=2048,
                                        d_feat=32)
        elif arch.family == "recsys":
            # keep embed_dim (DLRM ties it to bot_mlp[-1]); shrink tables
            arch = dataclasses.replace(arch, model=arch.model.scaled(
                vocab_sizes=tuple(min(v, 2000) for v in
                                  arch.model.vocab_sizes)))
            shape = dataclasses.replace(shape, batch=min(shape.batch, 64))
    set_policy(mesh)
    init_fn = _make_init(arch, shape, mesh or _FakeMesh())
    opt = default_optimizer(total_steps=steps, base_lr=lr)
    opt_init, _ = opt
    step_fn = make_train_step(arch, shape, optimizer=opt,
                              microbatches=microbatches)

    if arch.family == "lm":
        from repro.data.pipeline import TokenStream
        ds = TokenStream(arch.model.vocab_size, shape.seq_len,
                         shape.global_batch)
        data_gen = ds.batch_at
    elif arch.family == "recsys":
        from repro.data.pipeline import ClickStream, SasrecStream
        ds = (SasrecStream(arch.model, shape.batch)
              if arch.model.kind == "sasrec"
              else ClickStream(arch.model, shape.batch))
        data_gen = ds.batch_at
    else:
        from repro.data.pipeline import make_graph
        g = make_graph(shape.n_nodes, max(2, shape.n_edges // shape.n_nodes),
                       shape.d_feat, arch.model.n_classes)
        data_gen = lambda step: g

    shardings = None
    if mesh is not None:
        rule = {"lm": SH.lm_param_rule, "gnn": SH.gnn_param_rule,
                "recsys": SH.rec_param_rule}[arch.family](mesh)
        p_shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        p_specs = SH.spec_tree(p_shapes, rule)
        o_shapes = jax.eval_shape(opt_init, p_shapes)
        o_specs = SH.opt_state_specs(p_specs, p_shapes, o_shapes)
        state_specs = TrainState(p_specs, o_specs)
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 state_specs,
                                 is_leaf=lambda x: isinstance(x, P))

    def state_init(rng=None):
        params = init_fn(rng if rng is not None else jax.random.PRNGKey(0))
        st = TrainState(params, opt_init(params))
        if shardings is not None:
            st = jax.tree.map(lambda x, s: jax.device_put(x, s), st,
                              shardings)
        return st

    jit_step = jax.jit(step_fn, donate_argnums=(0,)) if mesh is None else \
        jax.jit(step_fn, in_shardings=(shardings, None),
                out_shardings=(shardings, None), donate_argnums=(0,))
    return arch, state_init, jit_step, data_gen, shardings


def _make_init(arch, shape, mesh):
    if arch.family == "lm":
        from repro.models.transformer import init_lm
        ep = mesh.shape.get("model", 1)
        return functools.partial(init_lm, cfg=arch.model, ep=ep)
    if arch.family == "gnn":
        from repro.models.gnn import init_gnn
        return functools.partial(init_gnn, cfg=arch.model, d_feat=shape.d_feat)
    from repro.models.recsys import init_recsys
    return functools.partial(init_recsys, cfg=arch.model)


class _FakeMesh:
    shape = {"model": 1}
    axis_names = ()


def train_loop(arch_id: str, shape_name: str, *, steps: int,
               ckpt_dir: Optional[str] = None, ckpt_every: int = 20,
               mesh=None, scale: str = "tiny", resume: bool = True,
               fail_at_step: Optional[int] = None, verbose: bool = True,
               lr: float = 3e-3):
    """Run training with checkpoint/restart support. Returns history dict.

    `fail_at_step` injects a WorkerFailure (fault-tolerance tests/demos)."""
    arch, state_init, jit_step, data_gen, shardings = build_trainer(
        arch_id, shape_name, mesh=mesh, scale=scale, steps=steps, lr=lr)
    start = 0
    state = state_init()
    if ckpt_dir and resume and latest_step(ckpt_dir) is not None:
        start = latest_step(ckpt_dir)
        state = restore(ckpt_dir, state, shardings=shardings)
        if verbose:
            print(f"[train] resumed from step {start}")
    ck = AsyncCheckpointer()
    hb = Heartbeat(ckpt_dir, 0) if ckpt_dir else None
    losses = []
    t0 = time.time()
    for step in range(start, steps):
        batch = {k: jnp.asarray(v) for k, v in data_gen(step).items()}
        if fail_at_step is not None and step == fail_at_step:
            err = WorkerFailure(f"injected failure at step {step}")
            err.last_step = step
            raise err
        state, metrics = jit_step(state, batch)
        losses.append(float(metrics["loss"]))
        if hb:
            hb.beat(step)
        if ckpt_dir and (step + 1) % ckpt_every == 0:
            ck.save(ckpt_dir, state, step=step + 1)
        if verbose and step % 10 == 0:
            print(f"[train] step {step} loss {losses[-1]:.4f} "
                  f"({(time.time() - t0):.1f}s)", flush=True)
    ck.wait()
    if ckpt_dir:
        ck.save(ckpt_dir, state, step=steps)
        ck.wait()
    return {"losses": losses, "final_step": steps,
            "wall_s": time.time() - t0}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--scale", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args(argv)
    hist = train_loop(args.arch, args.shape, steps=args.steps,
                      ckpt_dir=args.ckpt_dir, scale=args.scale, lr=args.lr)
    print(f"final loss {hist['losses'][-1]:.4f} after {args.steps} steps "
          f"in {hist['wall_s']:.1f}s")


if __name__ == "__main__":
    main()
