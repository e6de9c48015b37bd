"""The plain reference of one training cell's first steps, f32 throughout.

Each step: W workers draw their token rows, compute their loss and
gradient with the family's plain model, the Byzantine workers send
-3 x their gradient (sign flip), Algorithm 1's filter (``guard``) keeps
the rows it trusts, their mean is clipped to global norm ``grad_clip`` and
taken by AdamW (f32 moments, constant learning rate, no weight decay), and
the weights are stored back in the configuration's parameter dtype.  The
guard's running sums B are stored in the cell's statistics dtype, as the
cell states; every contraction is f32 at "highest" (or fp8 for the
control).  Inner products, Grams and norms over the whole model are sums
over its weights.

``reference_run`` returns per-step readings and, after the last step, the
norm of each weight's first moment and of its change, keyed by the weight's
path in the parameter tree.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import guard
from perfbench.reference.common import (
    HIGHEST,
    _is_leaf,
    byzantine_mask,
    contraction,
    init_tree,
    run_keys,
    token_batch,
)

F32 = jnp.float32
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
GUARD_DELTA, GUARD_D, V_EMA = 1e-3, 10.0, 0.9
SIGN_FLIP_SCALE = 3.0


class _State(NamedTuple):
    """Trees shaped like the parameters, the worker-stacked ones with a
    leading W."""

    x: dict             # weights, parameter dtype
    m: dict             # f32 first moment
    v: dict             # f32 second moment
    A: jax.Array        # (W,) f32
    B: dict             # (W, ...) statistics dtype
    alive: jax.Array    # (W,) bool
    v_est: jax.Array    # () f32
    k: jax.Array        # () int32 steps done


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((a.ndim - 1,), (b.ndim - 1,)), ((), ())),
                               precision=HIGHEST, preferred_element_type=F32)


def leaf_paths(defs) -> list[str]:
    """Key paths of the parameter tree, in flatten order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(defs, is_leaf=_is_leaf)
    return [jax.tree_util.keystr(p) for p, _ in flat]


class Reference(NamedTuple):
    """The reference's jitted pieces: ``init(seed) -> (weights, byz mask)``,
    ``step(state, x0, byz, seed, i) -> (state, readings)`` and
    ``state0(x0)``; ``paths`` names the weights in flatten order."""

    init: object
    step: object
    state0: object
    paths: list


def build(family, m: dict, wl: dict, *, precision: str = "f32",
          worker_batch: int | None = None, fault: str | None = None) -> Reference:
    """The reference of one cell.  ``family`` is a reference model module
    (``defs``, ``loss``); ``m`` the configuration's sizes; ``wl`` the
    cell's traffic (workers, per_worker_batch, seq_len, alpha, lr,
    grad_clip, planned_steps, stats_dtype).  ``precision`` is ``'f32'`` or
    ``'fp8'`` (the control).  ``fault`` plants a fault the comparison must
    catch: ``'half_batch'`` (the loss of each row over its first half
    only) or ``'token'`` (every row's first token altered where the token
    stream produces it, so that the row continues from the altered one)."""
    mm = contraction(precision)
    W, b, S = wl["workers"], wl["per_worker_batch"], wl["seq_len"]
    V = m["vocab_size"]
    n_byz = int(wl["alpha"] * W)
    pdt = jnp.dtype(m["param_dtype"])
    sdt = jnp.dtype({"f32": "float32", "bf16": "bfloat16"}[wl["stats_dtype"]])
    defs = family.defs(m)
    tmap = jax.tree_util.tree_map
    leaves = jax.tree_util.tree_leaves

    def rows(t):
        return t.reshape(t.shape[0], -1)

    @jax.jit
    def init(seed):
        init_key, mask_key, _, _ = run_keys(seed)
        return init_tree(init_key, defs, pdt), byzantine_mask(mask_key, W, n_byz)

    def worker_loss(p32, tokens, labels):
        if fault == "half_batch":
            tokens, labels = tokens[..., :S // 2], labels[..., :S // 2]
        return family.loss(m, mm, p32, tokens, labels)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(st: _State, x0, byz, seed, i):
        tokens, labels = token_batch(seed, V, S, W, b, i,
                                     first_shift=int(fault == "token"))
        p32 = tmap(lambda t: t.astype(F32), st.x)
        losses, G = jax.lax.map(
            lambda tl: jax.value_and_grad(worker_loss)(p32, *tl),
            (tokens, labels), batch_size=worker_batch or W)
        flip = jnp.where(byz, -SIGN_FLIP_SCALE, 1.0)
        G = tmap(lambda g: g * flip.reshape((W,) + (1,) * (g.ndim - 1)), G)
        k = st.k + 1
        A = st.A + sum(_dot(rows(g), (x.astype(F32) - x1.astype(F32)).reshape(-1))
                       for g, x, x1 in zip(leaves(G), leaves(st.x), leaves(x0)))
        B = tmap(lambda bb, g: (bb.astype(F32) + g).astype(sdt), st.B, G)
        gram_g = sum(_dot(rows(g), rows(g)) for g in leaves(G))
        gram_B = sum(_dot(rows(bb), rows(bb)) for bb in leaves(B))
        v_est = guard.estimate_v(gram_g, st.v_est, V_EMA)
        good = guard.filter_step(A, gram_B, gram_g, st.alive, k.astype(F32),
                                 v_est, T=wl["planned_steps"],
                                 delta=GUARD_DELTA, D=GUARD_D)
        w = good.astype(F32) / jnp.maximum(jnp.sum(good), 1)
        xi = tmap(lambda g: jnp.einsum("w,w...->...", w, g, precision=HIGHEST), G)
        nrm = jnp.sqrt(sum(jnp.sum(t * t) for t in leaves(xi)))
        scale = jnp.minimum(1.0, wl["grad_clip"] / jnp.maximum(nrm, 1e-30))
        t = k.astype(F32)
        mom = tmap(lambda mo, g: ADAM_B1 * mo + (1 - ADAM_B1) * g * scale, st.m, xi)
        vel = tmap(lambda ve, g: ADAM_B2 * ve + (1 - ADAM_B2) * jnp.square(g * scale),
                   st.v, xi)
        x = tmap(lambda x, mo, ve: (x.astype(F32) - wl["lr"] * (
            (mo / (1 - ADAM_B1 ** t))
            / (jnp.sqrt(ve / (1 - ADAM_B2 ** t)) + ADAM_EPS))).astype(pdt),
            st.x, mom, vel)
        readings = {
            "loss_good": jnp.sum(jnp.where(byz, 0.0, losses)) / max(W - n_byz, 1),
            "n_alive": jnp.sum(good),
            "byz_alive": jnp.sum(good & byz),
            "good_filtered": jnp.sum(~good & ~byz),
        }
        return _State(x, mom, vel, A, B, good, v_est, k), readings

    def state0(x0):
        zeros = lambda dt, lead=(): tmap(lambda t: jnp.zeros(lead + t.shape, dt), x0)
        return _State(tmap(jnp.copy, x0), zeros(F32), zeros(F32),
                      jnp.zeros((W,), F32), zeros(sdt, (W,)),
                      jnp.ones((W,), bool), jnp.zeros((), F32),
                      jnp.zeros((), jnp.int32))

    return Reference(init, step, state0, leaf_paths(defs))


def run(ref: Reference, seed: int, n_steps: int) -> dict:
    """The first ``n_steps`` steps of ``ref`` from ``seed``: per-step
    readings, and on the host, weight by weight, the first moment and the
    change of the weights after the last step, in f32."""
    seed_u32 = jnp.uint32(seed)
    x0, byz = ref.init(seed_u32)
    st = ref.state0(x0)
    per_step = []
    for i in range(n_steps):
        st, r = ref.step(st, x0, byz, seed_u32, jnp.int32(i))
        per_step.append({k: float(v) for k, v in jax.device_get(r).items()})
    leaves = lambda t: [np.asarray(v, np.float32)
                        for v in jax.tree_util.tree_leaves(jax.device_get(t))]
    return {
        "steps": {k: [r[k] for r in per_step] for k in per_step[0]},
        "m": dict(zip(ref.paths, leaves(st.m))),
        "dx": {k: a - b for k, a, b in zip(ref.paths, leaves(st.x), leaves(x0))},
    }


def reference_run(family, m: dict, wl: dict, seed: int, n_steps: int,
                  **options) -> dict:
    """:func:`run` of the reference :func:`build` makes."""
    return run(build(family, m, wl, **options), seed, n_steps)
