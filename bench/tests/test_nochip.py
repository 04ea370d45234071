"""Without a TPU a run measures nothing: it exits non-zero and prints no
result line; and a tree of only BENCHMARK.json and bench/ runs nothing."""

import json
import os
import shutil
import subprocess
import sys

import tiny


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ml1m-r32.wave-fit",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "correct" not in obj and "metrics" not in obj


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run(tiny.ROOT, env)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    _no_result(proc.stdout)


def test_bare_tree_runs_nothing(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run(tmp_path, env)
    assert proc.returncode != 0
    _no_result(proc.stdout)
