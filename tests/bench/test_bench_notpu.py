"""The command refuses to run without a TPU, and without the program."""
import json
import os
import shutil
import subprocess
import sys

import _bench_tiny  # noqa: F401 (puts the checkout root on sys.path)
from bench import cells

ARGS = ["--workload", "s2.batch", "--seed", str(2 ** 33 + 3), "--seconds",
        "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_refuses_without_a_tpu():
    proc = _run(cells.ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    _no_result(proc)


def test_refuses_in_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path)
    for path in cells.benchmark()["paths"]:
        shutil.copytree(os.path.join(cells.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    _no_result(proc)
