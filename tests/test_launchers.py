"""Smoke tests for the production launchers (subprocess, tiny configs)."""
import os
import subprocess
import sys

ENV = dict(os.environ, PYTHONPATH="src")


def _env(tmp_path):
    """The launchers turn on JAX's compile cache; keep it out of the
    checkout."""
    return dict(ENV, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))


def test_train_launcher_runs_and_checkpoints(tmp_path):
    ck = str(tmp_path / "ck")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "h2o-danube-1.8b", "--reduced", "--steps", "6", "--batch", "2",
         "--seq", "32", "--ckpt", ck, "--hoist"],
        env=_env(tmp_path), cwd=os.getcwd(), capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done" in out.stdout
    assert os.path.exists(os.path.join(ck, "LATEST"))
    # resume path
    out2 = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "h2o-danube-1.8b", "--reduced", "--steps", "8", "--batch", "2",
         "--seq", "32", "--ckpt", ck],
        env=_env(tmp_path), cwd=os.getcwd(), capture_output=True,
        text=True, timeout=900)
    assert out2.returncode == 0, out2.stderr[-2000:]
    assert "resumed from step" in out2.stdout


def test_serve_launcher_generates(tmp_path):
    # mamba2 exercises the chunked-prefill fallback (SSM -> 1 token/step).
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "mamba2-2.7b", "--reduced", "--requests", "2", "--max-batch", "2",
         "--prompt-len", "4", "--new-tokens", "6", "--cache-len", "32"],
        env=_env(tmp_path), cwd=os.getcwd(), capture_output=True,
        text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "tok/s" in out.stdout
    assert "[continuous]" in out.stdout
    assert "req 1 " in out.stdout
