"""The pieces of the chip path that can be checked without a chip: where
the compile cache goes, the per-device peak table, ``chip_smoke.py``'s
refusal to run off the TPU, and its sharded-vs-one-device comparison on a
one-device CPU mesh."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache
from repro.roofline import hw

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_uses_env_dir(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing else is set
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_inside_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_peak_table_is_keyed_by_device_kind():
    assert hw.peaks_for("TPU v5 lite") is hw.TPU_V5E
    assert hw.TPU_V5E.hbm_bw == 819e9 and hw.TPU_V5E.peak_flops_bf16 == 197e12
    with pytest.raises(KeyError, match="no peak row"):
        hw.peaks_for("cpu")


def test_chip_smoke_refuses_without_a_tpu(tmp_path):
    """Off the chip — and alone in a directory — the script exits non-zero
    and prints no result line."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_sharded_phase_matches_one_device(monkeypatch):
    """The --chips 4 comparison, run on a one-device CPU mesh at reduced
    size: the placed step and the plain step agree step for step."""
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    # CPU devices report no allocator statistics
    monkeypatch.setattr(chip_smoke.Smoke, "peak_bytes", lambda self, d: 0)
    smoke = chip_smoke.Smoke(jax)
    chip_smoke.sharded_phase(smoke, jax.devices()[:1], reduced=True,
                             workers=4, seq_len=32, steps=2)
    # one device needs no collectives; every other check passes
    assert smoke.failures == ["sharded step holds collectives"]
