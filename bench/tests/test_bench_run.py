"""The command refuses to run without a chip, or without the program."""

import json
import os
import shutil
import subprocess
import sys

from bench import spec


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    cell = spec.load_benchmark()["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    try:
        return not isinstance(json.loads(last), dict)
    except ValueError:
        return True


def test_refuses_a_machine_without_a_tpu():
    proc = _run(spec.ROOT)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert _no_result(proc)
    assert "TPU" in proc.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0 and _no_result(proc)
