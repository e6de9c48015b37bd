"""A run with the timed path broken underneath comes out not correct.

Drives ``harness.run_cell`` on the CPU at a tiny size (the run's look for
a chip is ``run.py``'s, and is skipped here), with each fault a training
cell can have planted in the program: the state returned unchanged, and
half of every row left out of the loss.  (One chip: there is no exchange
between chips to leave out.)  The unbroken run comes out correct.

The third fault, every row's first token altered where the token stream
produces it (``_ShiftedFirstToken``), is not caught by the numbers the
cell compares today: only ``moment_diff`` sees it, and that number has no
chip readings to set its limit from yet.  ``test_altered_token_moves_the
moments`` keeps the fault and shows the number that will catch it."""
import time

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness, registry
from repro.data.synthetic import SyntheticTokens

CELL = "mamba2-130m.w8.seq2048"
LIMITS = registry.workload(CELL)["limits"]


def _frozen(step):
    def broken(state, batch, rank, key):
        _, metrics = step(state, batch, rank, key)
        return state, metrics
    return broken


def _half_batch(step):
    def broken(state, batch, rank, key):
        S = batch["labels"].shape[-1]
        mask = (jnp.arange(S) < S // 2).astype(jnp.float32)
        batch = dict(batch, loss_mask=jnp.broadcast_to(mask, batch["labels"].shape))
        return step(state, batch, rank, key)
    return broken


class _ShiftedFirstToken(SyntheticTokens):
    """The program's token stream with every row's first token moved by one
    where the stream produces it; the rest of the row follows from it."""

    def sample(self, worker, step, batch, b_shift=0):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(self.seed), worker), step)
        k0, kn = jax.random.split(key)
        x0 = (jax.random.randint(k0, (batch,), 0, self.vocab_size) + 1) % self.vocab_size
        noise = jax.random.randint(kn, (batch, self.seq_len + 1), 0, self.noise_levels)

        def body(tok, n):
            nxt = (self.a * tok + self.b + b_shift + n) % self.vocab_size
            return nxt, nxt

        _, seq = jax.lax.scan(body, x0, noise.T)
        return seq.T


def _in_step(wrap):
    def plant(monkeypatch):
        real = harness.build_train_step
        monkeypatch.setattr(harness, "build_train_step",
                            lambda *a, **k: wrap(real(*a, **k)))
    return plant


def _token(monkeypatch):
    monkeypatch.setattr(harness, "SyntheticTokens", _ShiftedFirstToken)


FAULTS = {"state_unchanged": _in_step(_frozen), "half_batch": _in_step(_half_batch)}


@pytest.fixture
def tiny_cell(monkeypatch):
    base = registry.config("mamba2-130m")
    cfg = dict(base, model=dict(base["model"], n_layers=2, d_model=64,
                                vocab_size=512, ssm_state=16, ssm_head_dim=16,
                                ssm_chunk=32, param_dtype="float32",
                                activation_dtype="float32"))
    tr = dict(registry.traffic("w8.seq2048.chunk4"), workers=4, seq_len=64,
              stats_dtype="f32")
    monkeypatch.setattr(registry, "config", lambda name: cfg)
    monkeypatch.setattr(registry, "traffic", lambda name: tr)
    monkeypatch.setattr(registry, "workload",
                        lambda name: {"config": "c", "traffic": "t",
                                      "why": "", "limits": LIMITS})


def _run():
    bench = registry.benchmark()
    return harness.run_cell(CELL, 1234567, 0.2, False, time.perf_counter(),
                            bench, log=lambda s: None)


def test_unbroken_run_is_correct(tiny_cell):
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "setup_s"}   # no peak on the CPU


@pytest.mark.parametrize("fault", list(FAULTS))
def test_broken_run_is_not_correct(tiny_cell, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    r = _run()
    assert not r["correct"], r["checks"]


def test_altered_token_moves_the_moments(tiny_cell, monkeypatch):
    """The altered token leaves the filter decisions alone but turns the
    first moment's direction: ``moment_diff`` reads orders of magnitude
    above the unbroken run's."""
    sound = _moment_diff(monkeypatch)
    _token(monkeypatch)
    broken = _moment_diff(monkeypatch)
    assert broken > 100 * sound and broken > 0.01, (sound, broken)


def _moment_diff(monkeypatch):
    got = {}
    real = harness.compare.numbers

    def keep(prog, ref, g=None):
        got.update(real(prog, ref, g))
        return got

    monkeypatch.setattr(harness.compare, "numbers", keep)
    _run()
    return got["moment_diff"]
