"""CLI smoke tests for the launchers (build_index / serve / train)."""
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_cli(args, timeout=520, env_extra=None):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.update(env_extra or {})
    r = subprocess.run([sys.executable, "-m"] + args, env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    return r.stdout


def test_build_index_cli(tmp_path):
    out = run_cli(["repro.launch.build_index", "--out", str(tmp_path / "i"),
                   "--n", "600", "--dim", "32", "--R", "12", "--pq-m", "8",
                   "--build-L", "24"])
    assert "built" in out
    assert os.path.exists(tmp_path / "i" / "meta.json")


def test_build_index_sharded_cli(tmp_path):
    out = run_cli(["repro.launch.build_index", "--out", str(tmp_path / "s"),
                   "--n", "600", "--dim", "32", "--R", "12", "--pq-m", "8",
                   "--build-L", "24", "--shards", "2"])
    assert "2 shard indices" in out
    assert os.path.exists(tmp_path / "s" / "shard1" / "meta.json")


def test_serve_cli_demo():
    out = run_cli(["repro.launch.serve", "--queries", "24",
                   "--max-batch", "8"])
    assert "qps" in out and "p99" in out


def test_train_cli():
    out = run_cli(["repro.launch.train", "--arch", "dcn-v2",
                   "--shape", "train_batch", "--steps", "8"])
    assert "final loss" in out
