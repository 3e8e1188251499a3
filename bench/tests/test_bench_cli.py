"""The command refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from bench import spec

ARGS = ["--workload", "kron-bfs-uniform", "--seed", str(2 ** 31 + 11),
        "--seconds", "10", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-m", "bench.run", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_tpu():
    proc = _run(spec.ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_refuses_without_program(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
