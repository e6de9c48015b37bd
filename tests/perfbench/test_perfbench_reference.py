"""The plain references against the program, at small sizes on the CPU and
in f32, where both must agree to rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare, registry
from perfbench.reference import gqa_swiglu, guard, mamba2
from perfbench.reference.common import contraction, init_tree
from perfbench.reference.train import leaf_paths, reference_run

TINY = {
    "mamba2-130m": dict(n_layers=2, d_model=64, vocab_size=512, ssm_state=16,
                        ssm_head_dim=16, ssm_chunk=32),
    "internlm2-1.8b.l1v8": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                                head_dim=16, d_ff=128, vocab_size=512, attn_chunk=32),
}


def tiny(config: str, dtype: str = "float32") -> dict:
    base = registry.config(config)
    return dict(base, model=dict(base["model"], param_dtype=dtype,
                                 activation_dtype=dtype, **TINY[config]))


@pytest.mark.parametrize("config,family,tied", [("mamba2-130m", mamba2, False),
                                                ("mamba2-130m", mamba2, True),
                                                ("internlm2-1.8b.l1v8", gqa_swiglu, False)])
def test_model_loss_and_gradients_match_the_program(config, family, tied):
    """Tied: the LM head is the embedding's transpose, in both."""
    from repro.configs.base import ModelConfig
    from repro.models import build_model

    m = dict(tiny(config)["model"], tie_embeddings=tied)
    model = build_model(ModelConfig(**m))
    key = jax.random.PRNGKey(3)
    params = model.init(key)
    ref_params = init_tree(key, family.defs(m), jnp.float32)
    # the same recipe gives the same weights, leaf for leaf
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, m["vocab_size"])
    labels = jnp.roll(tokens, -1, axis=1)
    mm = contraction("f32")
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: model.loss_fn(
            p, {"tokens": tokens, "labels": labels})[0])(params)
        lr, gr = jax.value_and_grad(
            lambda p: family.loss(m, mm, p, tokens, labels))(ref_params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gp)[0],
                            jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-6 * float(jnp.max(jnp.abs(b))) + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))


def test_filter_matches_the_program_filter():
    from repro.core.byzantine_sgd import GuardConfig, filter_update
    from repro.distributed.byzantine_dp import v_from_gram

    W, d = 8, 300
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    base = jax.random.normal(k[0], (d,))
    g = base + 0.3 * jax.random.normal(k[1], (W, d))
    g = g.at[:2].multiply(-3.0)                       # two sign-flippers
    B = 4 * base + jax.random.normal(k[2], (W, d))
    B = B.at[5].add(40.0)                             # one drifted sum
    A = jax.random.normal(k[3], (W,))
    gram_g, gram_B = g @ g.T, B @ B.T
    alive = jnp.ones((W,), bool).at[7].set(False)
    for step in (1, 5, 40):
        V = float(guard.estimate_v(gram_g, jnp.float32(0.0)))
        assert V == pytest.approx(float(v_from_gram(gram_g)), rel=1e-6)
        cfg = GuardConfig(m=W, T=1000, V=V, D=10.0, delta=1e-3,
                          mean_over_alive=True)
        want, _ = filter_update(A, gram_B, gram_g, alive, jnp.int32(step), cfg)
        got = guard.filter_step(A, gram_B, gram_g, alive, jnp.float32(step), V,
                                T=1000, delta=1e-3, D=10.0)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not bool(got[0]) and not bool(got[1]) and not bool(got[7])


@pytest.mark.parametrize("config", list(TINY))
def test_first_chunk_matches_the_program(config):
    """The whole first chunk — data, per-worker gradients, ravel, sign-flip
    attack, dp_exact filter and aggregate, AdamW — agrees to f32 rounding."""
    from perfbench.harness import Program

    cfg = tiny(config)
    tr = dict(registry.traffic("w8.seq2048.chunk4"), workers=4, seq_len=64,
              stats_dtype="f32")
    prog = Program(cfg, tr)
    prog.start(2 ** 31 + 7)
    got = prog.first_chunk()
    del prog.state
    prog.weight_change(got)
    ref = reference_run(registry.reference_family(cfg["reference"]), cfg["model"],
                        tr, 2 ** 31 + 7, tr["log_every"])
    assert set(got["m"]) == set(got["dx"]) == set(ref["m"]) == set(leaf_paths(
        registry.reference_family(cfg["reference"]).defs(cfg["model"])))
    values = compare.numbers(got, ref)
    assert values["filter_gap"] == 0
    assert values["loss_gap"] < 1e-5
    assert max(values[k] for k in ("moment_gap", "update_gap", "moment_diff")) < 1e-4
