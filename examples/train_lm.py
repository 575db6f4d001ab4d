"""End-to-end driver (deliverable b): train a ~100M-param qwen3-family LM
for a few hundred steps with checkpointing + a mid-run simulated failure
and automatic restart.

    PYTHONPATH=src python examples/train_lm.py [--steps 200]

Runs the training launcher (`repro.launch.train`) on a 1-device CPU mesh
with a ~100M-parameter reduction of qwen3-1.7b.
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

from repro.configs import get_arch
from repro.distributed.fault_tolerance import run_with_restarts
from repro.launch import train as T


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a worker failure at this step")
    args = ap.parse_args()

    # ~100M params: 8L x d512 x ff2048, vocab 32k
    def scale_100m(cfg):
        return cfg.scaled(n_layers=8, d_model=512, n_heads=8, n_kv_heads=4,
                          head_dim=64, d_ff=2048, vocab_size=32768,
                          moe=None, dtype="float32")

    T.tiny_lm = scale_100m  # reuse launcher plumbing with the 100M scale
    arch = get_arch("qwen3-1.7b")
    print(f"training {scale_100m(arch.model).n_params()/1e6:.0f}M-param LM "
          f"for {args.steps} steps")
    ckpt = tempfile.mkdtemp(prefix="lm_ckpt_")

    injected = []

    def segment(resume):
        fail = args.fail_at if (args.fail_at and not injected) else None
        if fail:
            injected.append(True)
        return T.train_loop("qwen3-1.7b", "train_4k", steps=args.steps,
                            ckpt_dir=ckpt, ckpt_every=25,
                            fail_at_step=fail)["final_step"]

    final = run_with_restarts(segment, max_restarts=2,
                              on_restart=lambda n: print(
                                  f"[launcher] restart #{n} from checkpoint"))
    print(f"done at step {final}; checkpoints in {ckpt}")


if __name__ == "__main__":
    main()
