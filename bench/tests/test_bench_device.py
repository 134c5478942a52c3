"""The benchmark measures only on a TPU: anywhere else, or in a checkout
without the program, it exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

import tiny  # noqa: F401  (puts bench/ on the path)
from spec import BENCH, REPO

ARGS = ["--workload", "qwen15-decode", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(out: str) -> bool:
    lines = out.strip().splitlines()
    if not lines:
        return True
    try:
        json.loads(lines[-1])
    except ValueError:
        return True
    return False


def test_refuses_a_cpu():
    p = _run(REPO)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / BENCH.name,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "No module named 'repro'" in p.stderr
