"""The measuring path refuses a machine without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import run

ROOT = run.ROOT


def _args():
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    return ["--workload", cell["name"], "--seed", str(2**31 + 9),
            "--seconds", "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script] + _args(), cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_naming_the_backend_without_tpu():
    p = _run(ROOT, "bench/run.py")
    assert p.returncode != 0
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr
    assert not p.stdout.strip()


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "bench/run.py")
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        assert not line.startswith("{")
