"""Sharding-spec builders for whole train/serve states and input batches.

These produce (ShapeDtypeStruct tree, NamedSharding tree) pairs for AOT
lowering — the dry-run never allocates a byte.  Logical→mesh rules come
from :mod:`repro.distributed.sharding`; leaf kinds of caches / guard state
are resolved by field name + rank.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape
from repro.distributed.sharding import (
    LOGICAL_RULES_MULTI_POD,
    LOGICAL_RULES_SINGLE_POD,
    logical_to_spec,
    param_pspecs,
    use_logical_rules,
)
from repro.models.model import LanguageModel

PyTree = Any


def rules_for(shape: InputShape, multi_pod: bool, mesh: Mesh) -> dict:
    """Logical→mesh rules of a production step at ``shape`` — shared by
    the AOT dry-run and the four-chip run of ``chip_smoke.py``."""
    rules = dict(LOGICAL_RULES_MULTI_POD if multi_pod else LOGICAL_RULES_SINGLE_POD)
    # FSDP: shard the model-embed weight dim over the data axis (params are
    # otherwise replicated across workers — fatal at 76B+). Activations use
    # 'act_embed', so this touches weights only.
    rules["embed"] = "data"
    if shape.kind == "train":
        # inside the per-worker vmap the activation batch dim is the
        # *per-worker* batch; the worker axis already owns 'data' — sharding
        # both produces conflicting group shardings (XLA SPMD CHECK failure)
        rules["batch"] = None
    if shape.is_decode and shape.global_batch < mesh.shape.get("data", 1):
        # single-request long-context decode: batch can't use the data axis —
        # give it to the KV-cache sequence dim instead (flash-decoding style)
        rules["batch"] = None
        rules["cache_seq"] = ("data", "model")
    return rules


def _ns(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def _sds(shape, dtype, mesh, spec) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype, sharding=_ns(mesh, spec))


def _logical(axes, shape, rules, mesh) -> P:
    return logical_to_spec(tuple(axes), tuple(shape), rules, mesh)


# ---------------------------------------------------------------------------
# cache specs (decode/serve)
# ---------------------------------------------------------------------------

_CACHE_FIELD_AXES = {
    # field name → logical axes (leading 'None' = stacked layer axis)
    "k": (None, "batch", "cache_seq", "kv_heads", None),
    "v": (None, "batch", "cache_seq", "kv_heads", None),
    "ckv": (None, "batch", "cache_seq", None),
    "k_rope": (None, "batch", "cache_seq", None),
    "k_scale": (None, "batch", "cache_seq", "kv_heads"),
    "v_scale": (None, "batch", "cache_seq", "kv_heads"),
    "state": (None, "batch", "heads", None, None),
    "conv_x": (None, "batch", None, "mlp"),
    "conv_B": (None, "batch", None, None),
    "conv_C": (None, "batch", None, None),
    "pos": (),
}


def cache_specs(cache_abstract: PyTree, rules: dict, mesh: Mesh) -> PyTree:
    """PartitionSpec tree for an (abstract) decode cache."""

    def spec_for(path, leaf) -> P:
        name = None
        for pp in reversed(path):
            key = getattr(pp, "name", getattr(pp, "key", None))
            if isinstance(key, str):
                name = key
                break
        if name in _CACHE_FIELD_AXES and len(_CACHE_FIELD_AXES[name]) == leaf.ndim:
            return _logical(_CACHE_FIELD_AXES[name], leaf.shape, rules, mesh)
        # memory_kv tuples: (layers, B, Sm, H, hd)
        if leaf.ndim == 5:
            return _logical((None, "batch", None, "kv_heads", None), leaf.shape, rules, mesh)
        if leaf.ndim == 0:
            return P()
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec_for, cache_abstract)


# ---------------------------------------------------------------------------
# train-state specs
# ---------------------------------------------------------------------------

def _flat_state_specs(abstract: PyTree, W: int, rules: dict, mesh: Mesh) -> PyTree:
    """ShapeDtypeStructs-with-shardings for a tree-harness-era state pytree
    (guard backends + adversary/feedback leaves, DESIGN.md §10), by shape:

    * (W,)     — per-worker scalars: worker axes ('pod','data') — this is
                 also what the (m,) leaves of a
                 :class:`repro.scenarios.spec.WorkerProfile` resolve to
                 (skew f32, delay int32, p_report f32 all live on the
                 worker axis; DESIGN.md §13)
    * (W, W)   — filter-sized Grams: replicated
    * (W, d)   — the flat B martingale / sketch — and the trainer's
                 stale-gradient buffer: worker × flat_grad('model')
    * (d,)     — flat anchors/feedback vectors: flat_grad('model')
    * ()       — replicated

    Unsigned-integer 1-D leaves are PRNG keys (the bucketing aggregator
    carries a (2,) uint32 key in its state), not flat-gradient vectors —
    they must be replicated, never sharded along 'model' or 'worker'.
    """
    def one(a):
        shape = tuple(a.shape)
        if shape == ():
            spec = P()
        elif len(shape) == 1 and jnp.issubdtype(a.dtype, jnp.unsignedinteger):
            spec = P()
        elif shape == (W,):
            spec = _logical(("worker",), shape, rules, mesh)
        elif shape == (W, W):
            spec = P()
        elif len(shape) == 2 and shape[0] == W:
            spec = _logical(("worker", "flat_grad"), shape, rules, mesh)
        elif len(shape) == 1:
            spec = _logical(("flat_grad",), shape, rules, mesh)
        else:
            spec = P(*([None] * len(shape)))
        return _sds(shape, a.dtype, mesh, spec)

    return jax.tree_util.tree_map(one, abstract)


def make_train_specs(
    model: LanguageModel,
    cfg: "SolverConfig",
    optimizer_kind: str,
    shape: InputShape,
    rules: dict,
    mesh: Mesh,
    V: float = 0.0,
    D: float = 10.0,
    adversary=None,
):
    """(state_sds, batch_sds, rank_sds, rng_sds) ShapeDtypeStruct trees with
    shardings for AOT-lowering ``train_step``.

    ``cfg`` is the trainer's :class:`repro.core.solver.SolverConfig`
    (``guard_backend`` selects the aggregation realization); the guard /
    adversary / feedback leaves of :class:`repro.distributed.trainer.TrainState`
    are derived by ``eval_shape`` over the *same* factories the trainer
    uses, so the specs can never drift from the real state structure.
    """
    from repro.core.solver import make_aggregator
    from repro.core.tree_harness import FlatSpec, params_harness
    from repro.distributed.trainer import TrainState, _grad_dtype

    mcfg = model.cfg
    pdt = jnp.dtype(mcfg.param_dtype)
    W = cfg.m
    assert shape.global_batch % W == 0, (shape.global_batch, W)
    b = shape.global_batch // W

    with use_logical_rules(rules, mesh):
        pspecs = param_pspecs(model.defs, rules, mesh)
    params_sds = jax.tree_util.tree_map(
        lambda d, s: _sds(d.shape, pdt, mesh, s),
        model.defs, pspecs,
        is_leaf=lambda x: hasattr(x, "axes") and hasattr(x, "init"),
    )

    # optimizer state
    if optimizer_kind == "adamw":
        f32 = lambda t: jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=x.sharding), t
        )
        opt_sds = {"m": f32(params_sds), "v": f32(params_sds)}
    elif optimizer_kind == "momentum":
        opt_sds = {"m": jax.tree_util.tree_map(lambda x: x, params_sds)}
    else:
        opt_sds = {}

    harness = params_harness(model)
    fspec = FlatSpec(harness.d, V, D)
    guard_abs = jax.eval_shape(lambda: make_aggregator(fspec, cfg)[0])
    guard_sds = _flat_state_specs(guard_abs, W, rules, mesh)
    # adversary memory mirrors init_train_state: AdvState pytree under a
    # scenario adversary, a scalar zero on the static path — derived from
    # the same init so scenario runs lower against matching specs
    adv_abs = jax.eval_shape(
        (lambda: adversary.init_state(W, harness.d)) if adversary is not None
        else (lambda: jnp.zeros(()))
    )
    adv_sds = _flat_state_specs(adv_abs, W, rules, mesh)

    worker_spec = _logical(("worker",), (W,), rules, mesh)
    flat_spec = _logical(("flat_grad",), (harness.d,), rules, mesh)
    # stale-gradient buffer (DESIGN.md §13): present exactly when
    # init_train_state carries one — a (W, d) leaf sharded worker ×
    # flat_grad like the guard's B martingale; the schedule scalars that
    # drive it (cfg.max_delay) are static, nothing to shard
    stale_on = (getattr(adversary, "profile", None) is not None
                and cfg.max_delay > 0)
    grad_buf_sds = (_flat_state_specs(
        jax.ShapeDtypeStruct((W, harness.d), _grad_dtype(cfg, harness)),
        W, rules, mesh,
    ) if stale_on else ())
    state_sds = TrainState(
        params=params_sds,
        opt_state=opt_sds,
        guard=guard_sds,
        anchor=_sds((harness.d,), harness.flat_dtype, mesh, flat_spec),
        step=_sds((), jnp.int32, mesh, P()),
        ever_byz=_sds((W,), jnp.bool_, mesh, worker_spec),
        adv=adv_sds,
        prev_xi=_sds((harness.d,), harness.flat_dtype, mesh, flat_spec),
        prev_alive=_sds((W,), jnp.bool_, mesh, worker_spec),
        prev_n_alive=_sds((), jnp.int32, mesh, P()),
        grad_buf=grad_buf_sds,
    )

    batch_spec = _logical(("worker", None, None), (W, b, shape.seq_len), rules, mesh)
    batch_sds = {
        "tokens": _sds((W, b, shape.seq_len), jnp.int32, mesh, batch_spec),
        "labels": _sds((W, b, shape.seq_len), jnp.int32, mesh, batch_spec),
    }
    if mcfg.frontend != "none":
        fshape = (W, b, mcfg.frontend_seq if not mcfg.enc_dec else mcfg.enc_seq_len, mcfg.frontend_dim)
        batch_sds["frontend"] = _sds(
            fshape, jnp.dtype(mcfg.activation_dtype), mesh,
            _logical(("worker", None, None, None), fshape, rules, mesh),
        )
    rank_sds = _sds((W,), jnp.int32, mesh, worker_spec)
    rng_sds = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=_ns(mesh, P()))
    return state_sds, batch_sds, rank_sds, rng_sds


# ---------------------------------------------------------------------------
# serve specs
# ---------------------------------------------------------------------------

def make_serve_specs(
    model: LanguageModel, shape: InputShape, rules: dict, mesh: Mesh,
    cache_len: int | None = None,
):
    """(params_sds, cache_sds, token_sds) for AOT-lowering ``serve_step``."""
    cfg = model.cfg
    pdt = jnp.dtype(cfg.param_dtype)
    adt = jnp.dtype(cfg.activation_dtype)
    B = shape.global_batch
    L = cache_len if cache_len is not None else shape.seq_len

    with use_logical_rules(rules, mesh):
        pspecs = param_pspecs(model.defs, rules, mesh)
    params_sds = jax.tree_util.tree_map(
        lambda d, s: _sds(d.shape, pdt, mesh, s),
        model.defs, pspecs,
        is_leaf=lambda x: hasattr(x, "axes") and hasattr(x, "init"),
    )

    cache_abs = jax.eval_shape(lambda: model.init_cache(B, L, adt))
    cspecs = cache_specs(cache_abs, rules, mesh)
    cache_sds = jax.tree_util.tree_map(
        lambda a, s: _sds(a.shape, a.dtype, mesh, s), cache_abs, cspecs
    )
    token_sds = _sds((B, 1), jnp.int32, mesh, _logical(("batch", None), (B, 1), rules, mesh))
    return params_sds, cache_sds, token_sds


def make_prefill_specs(model: LanguageModel, shape: InputShape, rules: dict, mesh: Mesh):
    """(params_sds, batch_sds) for AOT-lowering ``prefill``."""
    cfg = model.cfg
    adt = jnp.dtype(cfg.activation_dtype)
    B, S = shape.global_batch, shape.seq_len
    params_sds, _, _ = make_serve_specs(model, shape, rules, mesh, cache_len=8)
    batch_sds = {
        "tokens": _sds((B, S), jnp.int32, mesh, _logical(("batch", None), (B, S), rules, mesh)),
    }
    if cfg.frontend != "none":
        F = cfg.frontend_seq if not cfg.enc_dec else cfg.enc_seq_len
        fshape = (B, F, cfg.frontend_dim)
        batch_sds["frontend"] = _sds(
            fshape, adt, mesh, _logical(("batch", None, None), fshape, rules, mesh)
        )
    return params_sds, batch_sds
