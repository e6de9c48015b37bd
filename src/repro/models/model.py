"""Model assembly: config → (param defs, init, loss_fn, prefill, decode_step).

Layer stacks are grouped into homogeneous :class:`BlockSpec` groups
(``cfg.layer_plan()``) and executed with ``lax.scan`` over parameters
stacked along a leading layer axis, each block wrapped in
``jax.checkpoint`` (full per-layer remat).  This keeps the HLO size
independent of depth (80-layer internvl2 compiles as fast as 2 layers) and
caps activation residency at one layer — both essential for the
512-device AOT dry-runs.

The LM loss is computed in sequence chunks with vocab-sharded logits so the
(B, S, 128k) logits tensor never materializes, not even for the backward
pass: each chunk is rematerialised (``jax.checkpoint``), so the backward
keeps only the chunk's inputs (hidden states, labels, mask) and recomputes
one chunk's logits at a time.  The gold logit is read with an ``iota == label``
select, whose transpose is elementwise, rather than a gather, whose
transpose is a scatter into a zeroed (B, c, V) buffer.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import BlockSpec, ModelConfig
from repro.distributed.sharding import shard_act
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.common import (
    ParamDef,
    abstract_params,
    cross_entropy,
    init_params,
    mlp_apply,
    mlp_defs,
    param_count,
    rms_norm,
)

# sequence chunk for the vocab-sharded CE loss; the backward keeps each
# chunk's inputs and recomputes its (B, LOSS_CHUNK, V) logits
LOSS_CHUNK = 512


# ---------------------------------------------------------------------------
# parameter definitions
# ---------------------------------------------------------------------------

def _norm_def(d: int) -> ParamDef:
    return ParamDef((d,), ("embed",), init="ones")


def _mixer_defs(spec: BlockSpec, cfg: ModelConfig) -> dict:
    if spec.mixer in ("attn", "swa"):
        return attn_lib.attn_defs(cfg)
    if spec.mixer == "mla":
        return attn_lib.mla_defs(cfg)
    if spec.mixer == "mamba":
        return ssm_lib.mamba_defs(cfg)
    raise ValueError(spec.mixer)


def _ff_defs(spec: BlockSpec, cfg: ModelConfig) -> dict:
    if spec.ff == "mlp":
        return mlp_defs(cfg.d_model, cfg.d_ff)
    if spec.ff == "moe":
        return moe_lib.moe_defs(cfg)
    if spec.ff == "none":
        return {}
    raise ValueError(spec.ff)


def _block_defs(spec: BlockSpec, cfg: ModelConfig, cross: bool) -> dict:
    d = {
        "norm1": _norm_def(cfg.d_model),
        "mixer": _mixer_defs(spec, cfg),
        "norm2": _norm_def(cfg.d_model),
        "ff": _ff_defs(spec, cfg),
    }
    if cross:
        d["cross_norm"] = _norm_def(cfg.d_model)
        d["cross"] = attn_lib.attn_defs(
            dataclasses.replace(cfg, n_kv_heads=cfg.n_heads)
        )
    return d


def _stack_defs(defs: dict, n: int) -> dict:
    return jax.tree_util.tree_map(
        lambda d: d.with_leading(n), defs, is_leaf=lambda x: isinstance(x, ParamDef)
    )


def model_defs(cfg: ModelConfig) -> dict:
    """Full ParamDef tree for the model."""
    defs: dict[str, Any] = {
        # the embed table's d_model dim uses its own logical axis
        # ('embed_table') that is never FSDP-sharded: its gradient is a
        # scatter-add (backward of the token gather), and XLA's SPMD
        # partitioner cannot handle scatter operands sharded on two axes.
        # The table is small (≤2.3GB bf16 across the pool), so vocab→model
        # sharding alone is plenty.
        "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed_table"), init="embed"),
        "final_norm": _norm_def(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    defs["groups"] = [
        _stack_defs(_block_defs(spec, cfg, cross=cfg.enc_dec), spec.count)
        for spec in cfg.layer_plan()
    ]
    if cfg.frontend != "none" and not cfg.enc_dec:
        defs["frontend_proj"] = ParamDef((cfg.frontend_dim, cfg.d_model), (None, "embed"))
    if cfg.enc_dec:
        enc_spec = BlockSpec(mixer="attn", ff="mlp", count=cfg.n_enc_layers)
        defs["enc"] = {
            "proj": ParamDef((cfg.frontend_dim or cfg.d_model, cfg.d_model), (None, "embed")),
            "group": _stack_defs(_block_defs(enc_spec, cfg, cross=False), cfg.n_enc_layers),
            "norm": _norm_def(cfg.d_model),
        }
    return defs


# ---------------------------------------------------------------------------
# forward blocks
# ---------------------------------------------------------------------------

def _apply_mixer(spec: BlockSpec, cfg: ModelConfig, p: dict, x, positions):
    if spec.mixer == "attn":
        return attn_lib.gqa_apply(p, cfg, x, positions, window=None)
    if spec.mixer == "swa":
        return attn_lib.gqa_apply(p, cfg, x, positions, window=cfg.sliding_window)
    if spec.mixer == "mla":
        return attn_lib.mla_apply(p, cfg, x, positions)
    if spec.mixer == "mamba":
        return ssm_lib.mamba_apply(p, cfg, x)
    raise ValueError(spec.mixer)


def _apply_ff(spec: BlockSpec, cfg: ModelConfig, p: dict, x):
    if spec.ff == "mlp":
        return mlp_apply(p, x), jnp.float32(0.0)
    if spec.ff == "moe":
        return moe_lib.moe_apply(p, cfg, x)
    return jnp.zeros_like(x), jnp.float32(0.0)


def _block_apply(spec: BlockSpec, cfg: ModelConfig, p: dict, x, positions, memory_kv=None):
    """One transformer block (pre-norm residual). Returns (x, aux)."""
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    x = x + _apply_mixer(spec, cfg, p["mixer"], h, positions)
    if memory_kv is not None:
        h = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        x = x + attn_lib.cross_attn_apply(p["cross"], cfg, h, *memory_kv)
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    ff, aux = _apply_ff(spec, cfg, p["ff"], h)
    x = x + ff
    x = shard_act(x, "batch", "act_seq", "act_embed")
    return x, aux


def _run_groups(cfg: ModelConfig, groups_params, x, positions, memory=None, enc_cross_p=None):
    """Scan each homogeneous group with per-layer remat. Returns (x, aux)."""
    aux_total = jnp.float32(0.0)
    for spec, gp in zip(cfg.layer_plan(), groups_params):
        @jax.checkpoint
        def body(carry, lp, spec=spec):
            xc, aux = carry
            mem_kv = None
            if memory is not None:
                mem_kv = attn_lib.project_memory(lp["cross"], memory)
            xc, a = _block_apply(spec, cfg, lp, xc, positions, mem_kv)
            return (xc, aux + a), None

        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), gp)
    return x, aux_total


def _run_encoder(cfg: ModelConfig, enc_params, frames):
    """Bidirectional encoder over frontend frames: (B, Sm, F) → (B, Sm, D)."""
    x = jnp.einsum("bsf,fd->bsd", frames, enc_params["proj"]).astype(frames.dtype)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)

    @jax.checkpoint
    def body(carry, lp):
        xc = carry
        h = rms_norm(xc, lp["norm1"], cfg.norm_eps)
        xc = xc + attn_lib.encoder_attn_apply(lp["mixer"], cfg, h, positions)
        h = rms_norm(xc, lp["norm2"], cfg.norm_eps)
        xc = xc + mlp_apply(lp["ff"], h)
        return xc, None

    x, _ = jax.lax.scan(body, x, enc_params["group"])
    return rms_norm(x, enc_params["norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# losses (chunked, vocab-sharded)
# ---------------------------------------------------------------------------

def _lm_head(cfg: ModelConfig, params, h):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, w)
    return shard_act(logits, "batch", None, "vocab")


def _chunked_ce(cfg: ModelConfig, params, h, labels, mask):
    """CE over sequence chunks; h: (B,S,D), labels/mask: (B,S)."""
    B, S, D = h.shape
    c = min(LOSS_CHUNK, S)
    n = S // c if S % c == 0 else 1
    c = S // n
    hc = h.reshape(B, n, c, D)
    lc = labels.reshape(B, n, c)
    mc = mask.reshape(B, n, c)

    @jax.checkpoint
    def body(carry, xs):
        tot, cnt = carry
        hh, ll, mm = xs
        logits = _lm_head(cfg, params, hh)
        logits32 = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits32, axis=-1)
        iota = jnp.arange(logits32.shape[-1], dtype=ll.dtype)
        gold = jnp.where(iota == ll[..., None], logits32, 0.0).sum(-1)
        nll = (lse - gold) * mm
        return (tot + jnp.sum(nll), cnt + jnp.sum(mm)), None

    xs = (jnp.moveaxis(hc, 1, 0), jnp.moveaxis(lc, 1, 0), jnp.moveaxis(mc, 1, 0))
    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), xs)
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _group_cache(spec: BlockSpec, cfg: ModelConfig, batch: int, length: int, dtype):
    if spec.mixer in ("attn", "swa"):
        L = min(length, cfg.sliding_window) if spec.mixer == "swa" and cfg.sliding_window else length
        one = attn_lib.init_kv_cache(cfg, batch, L, dtype)
    elif spec.mixer == "mla":
        one = attn_lib.init_mla_cache(cfg, batch, length, dtype)
    elif spec.mixer == "mamba":
        one = ssm_lib.init_mamba_cache(cfg, batch, dtype)
    else:
        raise ValueError(spec.mixer)
    return jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (spec.count, *a.shape)), one)


# ---------------------------------------------------------------------------
# public bundle
# ---------------------------------------------------------------------------

class LanguageModel(NamedTuple):
    cfg: ModelConfig
    defs: dict
    init: Callable            # (key) -> params
    abstract: Callable        # () -> ShapeDtypeStruct tree
    loss_fn: Callable         # (params, batch) -> (loss, metrics)
    forward: Callable         # (params, batch) -> hidden (B,S,D)
    prefill: Callable         # (params, batch, cache_len) -> (last_logits, cache)
    decode_step: Callable     # (params, cache, token, extras) -> (logits, cache)
    init_cache: Callable      # (batch, length, dtype) -> cache
    n_params: int


def build_model(cfg: ModelConfig) -> LanguageModel:
    defs = model_defs(cfg)
    pdt = jnp.dtype(cfg.param_dtype)
    adt = jnp.dtype(cfg.activation_dtype)

    # ----------------------------- train -----------------------------
    def forward(params, batch):
        tokens = batch["tokens"]
        x = params["embed"][tokens].astype(adt)
        prefix = 0
        if cfg.frontend != "none" and not cfg.enc_dec:
            fe = batch["frontend"].astype(adt)                    # (B, F, fd)
            fx = jnp.einsum("bfe,ed->bfd", fe, params["frontend_proj"]).astype(adt)
            x = jnp.concatenate([fx, x], axis=1)
            prefix = fe.shape[1]
        x = shard_act(x, "batch", "act_seq", "act_embed")
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        memory = None
        if cfg.enc_dec:
            memory = _run_encoder(cfg, params["enc"], batch["frontend"].astype(adt))
        x, aux = _run_groups(cfg, params["groups"], x, positions, memory=memory)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, aux, prefix

    def loss_fn(params, batch):
        h, aux, prefix = forward(params, batch)
        labels = batch["labels"]
        if prefix:
            h = h[:, prefix:]
        mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
        ce = _chunked_ce(cfg, params, h, labels, mask.astype(jnp.float32))
        loss = ce + cfg.router_aux_weight * aux
        return loss, {"ce": ce, "aux": aux}

    # ----------------------------- serve -----------------------------
    def prefill(params, batch, cache_len: int):
        """Process a full prompt; emit last-token logits + a decode cache."""
        tokens = batch["tokens"]
        x = params["embed"][tokens].astype(adt)
        if cfg.frontend != "none" and not cfg.enc_dec:
            fe = batch["frontend"].astype(adt)
            fx = jnp.einsum("bfe,ed->bfd", fe, params["frontend_proj"]).astype(adt)
            x = jnp.concatenate([fx, x], axis=1)
        x = shard_act(x, "batch", "act_seq", "act_embed")
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        pos_final = jnp.asarray(S, jnp.int32)
        memory = None
        if cfg.enc_dec:
            memory = _run_encoder(cfg, params["enc"], batch["frontend"].astype(adt))

        layer_caches, memory_kvs = [], []
        for spec, gp in zip(cfg.layer_plan(), params["groups"]):
            def body(carry, lp, spec=spec):
                xc = carry
                h = rms_norm(xc, lp["norm1"], cfg.norm_eps)
                if spec.mixer in ("attn", "swa"):
                    win = cfg.sliding_window if spec.mixer == "swa" else None
                    o, (k, v) = attn_lib.gqa_apply(
                        lp["mixer"], cfg, h, positions, window=win, return_kv=True
                    )
                    L = min(cache_len, win) if win else cache_len
                    lc = attn_lib.cache_from_prefill(
                        k, v, L, pos_final, quantize=cfg.kv_cache_dtype == "int8"
                    )
                elif spec.mixer == "mla":
                    o, (c, kr) = attn_lib.mla_apply(lp["mixer"], cfg, h, positions, return_kv=True)
                    lc = attn_lib.mla_cache_from_prefill(c, kr, cache_len, pos_final)
                else:
                    o, lc = ssm_lib.mamba_apply(lp["mixer"], cfg, h, return_state=True)
                xc = xc + o
                mem_kv = None
                if cfg.enc_dec:
                    hh = rms_norm(xc, lp["cross_norm"], cfg.norm_eps)
                    mem_kv = attn_lib.project_memory(lp["cross"], memory)
                    xc = xc + attn_lib.cross_attn_apply(lp["cross"], cfg, hh, *mem_kv)
                h = rms_norm(xc, lp["norm2"], cfg.norm_eps)
                ff, _ = _apply_ff(spec, cfg, lp["ff"], h)
                ys = (lc, mem_kv) if cfg.enc_dec else (lc,)
                return xc + ff, ys

            x, ys = jax.lax.scan(body, x, gp)
            layer_caches.append(ys[0])
            if cfg.enc_dec:
                memory_kvs.append(ys[1])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _lm_head(cfg, params, x[:, -1:, :])
        cache = {"layers": layer_caches}
        if cfg.enc_dec:
            cache["memory_kv"] = memory_kvs
        return logits, cache

    def init_cache(batch: int, length: int, dtype=None):
        dtype = dtype or adt
        cache = {
            "layers": [
                _group_cache(spec, cfg, batch, length, dtype)
                for spec in cfg.layer_plan()
            ]
        }
        if cfg.enc_dec:
            kv, hd = cfg.n_heads, cfg.head_dim  # cross attn uses full heads
            n_dec = cfg.n_layers
            cache["memory_kv"] = [
                (
                    jnp.zeros((spec.count, batch, cfg.enc_seq_len, kv, hd), dtype),
                    jnp.zeros((spec.count, batch, cfg.enc_seq_len, kv, hd), dtype),
                )
                for spec in cfg.layer_plan()
            ]
        return cache

    def decode_step(params, cache, token, extras=None):
        """token: (B, 1) int32 → (logits (B, 1, V), cache')."""
        x = params["embed"][token].astype(adt)
        new_layers = []
        for gi, (spec, gp) in enumerate(zip(cfg.layer_plan(), params["groups"])):
            gcache = cache["layers"][gi]
            mem = cache.get("memory_kv")[gi] if cfg.enc_dec else None

            def body(carry, xs, spec=spec, mem_static=cfg.enc_dec):
                xc = carry
                if mem_static:
                    lp, lc, mk, mv = xs
                else:
                    lp, lc = xs
                h = rms_norm(xc, lp["norm1"], cfg.norm_eps)
                if spec.mixer in ("attn", "swa"):
                    o, lc = attn_lib.gqa_decode_apply(lp["mixer"], cfg, h, lc)
                elif spec.mixer == "mla":
                    o, lc = attn_lib.mla_decode_apply(lp["mixer"], cfg, h, lc)
                else:
                    o, lc = ssm_lib.mamba_decode_apply(lp["mixer"], cfg, h, lc)
                xc = xc + o
                if mem_static:
                    hh = rms_norm(xc, lp["cross_norm"], cfg.norm_eps)
                    xc = xc + attn_lib.cross_attn_apply(lp["cross"], cfg, hh, mk, mv)
                h = rms_norm(xc, lp["norm2"], cfg.norm_eps)
                ff, _ = _apply_ff(spec, cfg, lp["ff"], h)
                return xc + ff, lc

            xs = (gp, gcache, *cache["memory_kv"][gi]) if cfg.enc_dec else (gp, gcache)
            x, new_cache = jax.lax.scan(body, x, xs)
            new_layers.append(new_cache)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _lm_head(cfg, params, x)
        new = dict(cache)
        new["layers"] = new_layers
        return logits, new

    return LanguageModel(
        cfg=cfg,
        defs=defs,
        init=lambda key: init_params(key, defs, pdt),
        abstract=lambda: abstract_params(defs, pdt),
        loss_fn=loss_fn,
        forward=forward,
        prefill=prefill,
        decode_step=decode_step,
        init_cache=init_cache,
        n_params=param_count(defs),
    )
