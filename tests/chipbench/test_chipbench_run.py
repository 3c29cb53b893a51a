"""The command refuses a CPU device, and a checkout without the program."""
import os
import shutil
import subprocess
import sys

from tinybench import REPO

ARGS = ["--workload", "qwen2-0.5b.chat", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_a_cpu_device():
    out = _run(REPO)
    assert out.returncode == 1
    assert out.stdout == ""
    assert "not a TPU" in out.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(REPO / "tests" / "chipbench",
                    tmp_path / "tests" / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
