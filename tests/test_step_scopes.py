"""The train step's named scopes (``repro.obs.STEP_SCOPES``), read from
the compiled program as a device trace reads them.

A tiny bf16 mamba2 ``dp_exact`` step runs inside a 2-step ``lax.scan``,
its batches made on the device, as the benchmark's chunk program drives
it.  Each op's scope is the ``op_name`` of the optimised HLO
(``perfbench.trace.scope_map``), its layer ``perfbench.layers.layer_of``.
The scopes are metadata only: with ``jax.named_scope`` made a no-op the
optimised HLO is the same once metadata is stripped.
"""
import contextlib
import dataclasses
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.solver import SolverConfig, byz_rank
from repro.data.synthetic import SyntheticTokens, make_worker_batch
from repro.distributed.trainer import build_train_step, init_train_state
from repro.models import build_model
from repro.obs import STEP_SCOPES
from repro.optim import adamw

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from perfbench import layers, trace  # noqa: E402

W, SEQ = 4, 16
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")
# the source-location tables of the HLO text and every op's metadata
_TABLES = re.compile(r"^(\d+ |FileNames|FunctionNames|FileLocations|StackFrames)")
_METADATA = re.compile(r",? metadata=\{[^}]*\}")


def _compiled_text():
    """Optimised HLO of a fresh 2-step chunk program: tracing anew each
    call, so that a patched ``jax.named_scope`` takes effect."""
    cfg = dataclasses.replace(
        get_config("mamba2-130m").reduced(max_d_model=64, max_vocab=256),
        param_dtype="bfloat16", activation_dtype="bfloat16", ssm_chunk=8)
    model = build_model(cfg)
    opt = adamw(1e-3, grad_clip=1.0)
    scfg = SolverConfig(m=W, T=100, eta=1e-3, alpha=0.25,
                        aggregator="byzantine_sgd", attack="sign_flip",
                        mean_over_alive=True, guard_backend="dp_exact",
                        stats_dtype="bf16")
    step = build_train_step(model, opt, scfg)

    def chunk(state, idx, data_seed, rank, loop_key):
        stream = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=SEQ,
                                 seed=data_seed)

        def body(st, i):
            batch = make_worker_batch(stream, W, 1, i)
            return step(st, batch, rank, jax.random.fold_in(loop_key, i))

        return jax.lax.scan(body, state, idx)

    state = jax.eval_shape(lambda k: init_train_state(model, opt, scfg, k),
                           jax.random.PRNGKey(0))
    args = (np.arange(2, dtype=np.int32), np.uint32(0),
            byz_rank(jax.random.PRNGKey(1), W), jax.random.PRNGKey(2))
    text = jax.jit(chunk).lower(state, *args).compile().as_text()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return text, params


@pytest.fixture(scope="module")
def program():
    text, params = _compiled_text()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        unscoped, _ = _compiled_text()
    return text, unscoped, params


def _instructions(text):
    """[(opcode, shape, op_name)] of every instruction that has metadata,
    fused ones included."""
    scopes = trace.scope_map(text)
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and m.group(1) in scopes:
            out.append((m.group(4), (m.group(2), m.group(3)), scopes[m.group(1)]))
    return out


@pytest.mark.parametrize("prefix", STEP_SCOPES)
def test_every_layer_is_named(program, prefix):
    assert any(prefix in s for s in trace.scope_map(program[0]).values())


def test_guard_b_update_is_a_statistic(program):
    text, _, _ = program
    d = re.search(r"state_guard_B\.\d+: bf16\[(\d+,\d+)\]", text).group(1)
    adds = [s for op, (_, shape), s in _instructions(text)
            if op == "add" and shape == d]
    assert adds and all("guard/stats_sweep/" in s for s in adds)
    assert {layers.layer_of(s) for s in adds} == {"guard"}


def test_optimizer_sqrt_is_the_optimizer(program):
    text, _, params = program
    weights = {",".join(map(str, p.shape))
               for p in jax.tree_util.tree_leaves(params) if p.ndim == 2}
    sqrts = [s for op, (dt, shape), s in _instructions(text)
             if op == "sqrt" and dt == "f32" and shape in weights]
    assert sqrts and all("optim/update/" in s for s in sqrts)
    assert {layers.layer_of(s) for s in sqrts} == {"optim"}


def test_forward_and_backward_apart(program):
    scopes = [s for s in trace.scope_map(program[0]).values() if "model/" in s]
    fwd = [s for s in scopes if layers.layer_of(s) == "model_fwd"]
    bwd = [s for s in scopes if layers.layer_of(s) == "model_bwd"]
    assert fwd and len(bwd) > len(fwd)
    # the forward holds nothing of the VJP; remat's recomputed forward
    # runs inside the backward and is classed with it
    assert not any("transpose(" in s or "checkpoint" in s for s in fwd)
    remat = [s for s in scopes if "rematted_computation" in s]
    assert remat and all(layers.layer_of(s) == "model_bwd" for s in remat)


def test_scopes_leave_the_program_unchanged(program):
    text, unscoped, _ = program
    assert not any(p in s for s in trace.scope_map(unscoped).values()
                   for p in STEP_SCOPES)

    def strip(t):
        body = "\n".join(l for l in t.splitlines() if not _TABLES.match(l))
        return _METADATA.sub("", body)

    assert strip(text) == strip(unscoped)
