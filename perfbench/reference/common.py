"""Pieces every plain reference shares: the seeded initialisation recipe,
the synthetic token stream, the Byzantine ranks, RMS norm, cross entropy,
and the contraction that the control lowers to fp8.

Nothing here imports the program.  The initialisation and the data follow
the recipes the trainer documents (per-leaf keys split in flatten order,
truncated normals scaled by 1/sqrt(fan-in); a Markov token stream keyed by
seed, worker and step), written out again from ``jax`` alone, so the
reference starts from the same weights and data without taking either from
the program.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


class Leaf(NamedTuple):
    """One parameter: shape, initialiser (normal | zeros | ones | embed)
    and the stddev multiplier of ``normal`` and ``embed``."""

    shape: tuple
    init: str = "normal"
    scale: float = 1.0


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def lm_defs(m: dict, block: dict) -> dict:
    """A language model's leaves: the embedding, the final norm, the LM
    head where the configuration does not tie it to the embedding, and
    ``n_layers`` of ``block``."""
    d, V = m["d_model"], m["vocab_size"]
    out = {"embed": Leaf((V, d), "embed"), "final_norm": Leaf((d,), "ones"),
           "groups": [stacked(block, m["n_layers"])]}
    if not m["tie_embeddings"]:
        out["lm_head"] = Leaf((d, V))
    return out


def stacked(tree: dict, n: int) -> dict:
    """The same leaves with a leading layer axis of ``n``."""
    return jax.tree_util.tree_map(
        lambda l: l._replace(shape=(n, *l.shape)), tree, is_leaf=_is_leaf)


def init_tree(key: jax.Array, defs, dtype) -> dict:
    """Weights from ``key``: one key per leaf, split in flatten order;
    ``normal`` leaves are truncated to ±2 and scaled by 1/sqrt(fan-in),
    where the fan-in is the second-to-last dimension."""
    leaves, treedef = jax.tree_util.tree_flatten(defs, is_leaf=_is_leaf)
    keys = jax.random.split(key, max(len(leaves), 1))
    out = []
    for k, d in zip(keys, leaves):
        if d.init == "zeros":
            out.append(jnp.zeros(d.shape, dtype))
        elif d.init == "ones":
            out.append(jnp.ones(d.shape, dtype))
        elif d.init == "embed":
            out.append((d.scale * jax.random.normal(k, d.shape)).astype(dtype))
        else:
            fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
            std = d.scale / np.sqrt(max(int(fan_in), 1))
            out.append((std * jax.random.truncated_normal(
                k, -2.0, 2.0, d.shape)).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def run_keys(seed: jax.Array):
    """(init, mask, data, loop) keys: one four-way split of the seed."""
    return jax.random.split(jax.random.PRNGKey(seed), 4)


def byzantine_mask(mask_key: jax.Array, workers: int, n_byz: int) -> jax.Array:
    """Worker w is Byzantine iff its place in a random permutation of the
    workers is below ``n_byz``."""
    rank = jnp.argsort(jax.random.permutation(mask_key, workers))
    return rank < n_byz


def token_batch(seed: jax.Array, vocab: int, seq_len: int, workers: int,
                per_worker: int, step: jax.Array, first_shift: int = 0):
    """(tokens, labels), each (W, b, S): worker w's rows at ``step`` follow
    t' = (31 t + 7 + n) mod V with n uniform in [0, 8), seeded by
    (seed, w, step).  ``first_shift`` moves each row's first token, from
    which the rest of the row follows."""
    def one(w):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(seed), w), step)
        k0, kn = jax.random.split(key)
        x0 = (jax.random.randint(k0, (per_worker,), 0, vocab) + first_shift) % vocab
        noise = jax.random.randint(kn, (per_worker, seq_len + 1), 0, 8)

        def body(tok, n):
            nxt = (31 * tok + 7 + n) % vocab
            return nxt, nxt

        _, seq = jax.lax.scan(body, x0, noise.T)
        return seq.T

    seqs = jax.vmap(one)(jnp.arange(workers))
    return seqs[..., :-1], seqs[..., 1:]


def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def cross_entropy(logits, labels):
    """Mean next-token negative log-likelihood, f32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# contractions: f32 at "highest" for the reference, fp8 for the control
# ---------------------------------------------------------------------------

def _fp8(x, dtype):
    """Per-tensor scaled rounding of ``x`` to ``dtype`` (an fp8 format),
    returned in f32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(F32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    return jnp.einsum(spec, _fp8(a, jnp.float8_e4m3fn),
                      _fp8(b, jnp.float8_e4m3fn), precision=HIGHEST)


def _einsum_fp8_fwd(spec, a, b):
    aq, bq = _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn)
    return jnp.einsum(spec, aq, bq, precision=HIGHEST), (aq, bq)


def _einsum_fp8_bwd(spec, res, g):
    # the usual fp8 training recipe: e4m3 operands forward, e5m2 gradients
    aq, bq = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     aq, bq)
    return vjp(_fp8(g, jnp.float8_e5m2))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def contraction(precision: str):
    """``einsum(spec, a, b)`` in f32 at "highest" (``precision='f32'``) or
    with fp8 operands and f32 accumulation (``'fp8'``, the control)."""
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        return _einsum_fp8
    raise ValueError(f"unknown reference precision {precision!r}")
