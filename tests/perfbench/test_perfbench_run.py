"""run.py refuses to measure anywhere but on the chip, and without the
program beside it; in both cases it prints no result line."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _no_result(p: subprocess.CompletedProcess) -> bool:
    last = (p.stdout.strip().splitlines() or [""])[-1]
    try:
        return "correct" not in json.loads(last)
    except ValueError:
        return True


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0 and _no_result(p)
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and _no_result(p)
    assert "holds no repro package" in p.stderr


def test_unknown_workload_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload", "nope",
                        "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode != 0 and _no_result(p)
