"""The examples/ scripts run end-to-end in smoke mode (subprocess, CPU
mesh) — the BASELINE.md configurations stay executable."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, *args, timeout=240) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=REPO)
    assert out.returncode == 0, f"stdout={out.stdout}\nstderr={out.stderr}"
    return out.stdout


def test_gpt2_ddp_example():
    out = _run("gpt2_ddp_train.py", "--steps", "2")
    assert "final:" in out and "loss" in out


def test_resnet_cifar_example():
    out = _run("resnet_cifar_train.py", "--steps", "2")
    assert "final:" in out


def test_ppo_example():
    out = _run("ppo_cartpole.py", "--iters", "2", "--target", "1")
    assert "best reward:" in out


def test_llama_serve_example():
    out = _run("llama_serve.py", "--requests", "3", "--max-new", "6",
               timeout=300)
    assert "generated token ids:" in out
    assert "ttft=" in out and "tok/s" in out


def test_llama_serve_example_legacy():
    out = _run("llama_serve.py", "--no-engine", timeout=300)
    assert "generated token ids:" in out


def test_llama_serve_example_tp():
    """--tp 2: the replica's engine lowers under a 2-chip mesh (the
    subprocess env already forces 8 host devices) and the per-chip KV
    occupancy print shows blocks resident on BOTH chips."""
    # 3 prompt + 20 new tokens span two 16-token blocks, and the pool
    # takes them from alternate chips: each request alone puts a block on
    # both, whether or not the three overlap (beside other work they may
    # run one after the other)
    out = _run("llama_serve.py", "--tp", "2", "--requests", "3",
               "--max-new", "20", timeout=300)
    assert "per-chip KV occupancy" in out
    assert "chip 0:" in out and "chip 1:" in out
    import re

    used = [int(m) for m in re.findall(r"chip \d: (\d+) blocks", out)]
    assert len(used) == 2 and all(u > 0 for u in used), out


def test_vit_pbt_example():
    out = _run("vit_pbt_sweep.py", "--population", "2", timeout=300)
    assert "best lr:" in out


def test_ppo_breakout_example():
    out = _run("ppo_breakout.py", "--workers", "1", "--iters", "1",
               "--target", "-1")
    assert "best reward:" in out


def test_gpt_pipeline_cgraph_example():
    out = _run("gpt_pipeline_cgraph.py", "--iters", "6", timeout=300)
    assert "tokens/s" in out


def test_ppo_jax_fused_example():
    out = _run("ppo_jax_fused.py", "--steps", "3", "--num-envs", "16",
               "--rollout-len", "16", "--iters-per-step", "2")
    assert "done:" in out and "steps/s" in out


def test_external_env_serving_example():
    out = _run("external_env_serving.py", "--clients", "1",
               "--seconds", "20", "--target", "15")
    assert "policy server listening" in out and "reward=" in out
