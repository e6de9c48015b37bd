"""The control — the plain reference at fp8, the precision below the
configuration's bf16, put in the program's place — and the planted faults
of the reference come out not correct under each cell's limits.

At a size a test run holds, on the CPU.  The readings at the cell's own
size, from which the limits were set, are in PERF.md (``perfbench/control.py``
makes them again); there the fp8 control and the altered token still pass
the numbers compared, an open question PERF.md names."""
import functools

import pytest

from perfbench import compare, registry
from perfbench.control import VARIANTS
from perfbench.reference.train import build, run

CELLS = [w["name"] for w in registry.benchmark()["workloads"]]
TINY = {
    "mamba2-130m": dict(n_layers=1, d_model=32, vocab_size=256, ssm_state=16,
                        ssm_head_dim=16, ssm_chunk=16),
    "internlm2-1.8b.l1v8": dict(n_layers=1, d_model=32, n_heads=4, n_kv_heads=2,
                                head_dim=8, d_ff=64, vocab_size=256),
}


@functools.lru_cache(maxsize=None)
def _setup(cell):
    wl = registry.workload(cell)
    cfg = registry.config(wl["config"])
    m = dict(cfg["model"], **TINY[wl["config"]])
    tr = dict(registry.traffic(wl["traffic"]), workers=4, seq_len=32, log_every=4)
    fam = registry.reference_family(cfg["reference"])
    return wl, fam, m, tr, run(build(fam, m, tr), SEED, tr["log_every"])


SEED = 2 ** 31 + 11


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("cell", CELLS)
def test_control_and_faults_fail_the_limits(cell, variant):
    wl, fam, m, tr, ref = _setup(cell)
    other = run(build(fam, m, tr, **VARIANTS[variant]), SEED, tr["log_every"])
    correct, checks = compare.check(compare.numbers(other, ref), wl["limits"])
    assert not correct, checks
