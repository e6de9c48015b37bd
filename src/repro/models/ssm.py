"""Mamba2 (SSD — state-space duality) mixer, arXiv:2405.21060.

Chunked SSD algorithm for train/prefill (quadratic *within* length-Q chunks,
linear recurrence *across* chunks → O(S·Q) work, O(1) state), and the exact
O(1)-per-token recurrence for decode. This is what makes ``long_500k``
native for mamba2/jamba: decode state is (H, N, P) regardless of context.

Projection layout: we split the fused in_proj of the reference CUDA
implementation into separate z/x/B/C/dt projections and give x, B, C their
own depthwise causal convs — functionally identical, but each output dim
then has a clean logical sharding axis (heads → 'model'), which is the TPU
adaptation of Mamba2's GPU-fused layout.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard_act
from repro.models.common import ParamDef, rms_norm


def mamba_defs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    di = cfg.d_inner_ssm
    H = cfg.n_ssm_heads
    G, N, W = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv_width
    return {
        "w_z": ParamDef((d, di), ("embed", "mlp")),
        "w_x": ParamDef((d, di), ("embed", "mlp")),
        "w_B": ParamDef((d, G * N), ("embed", None)),
        "w_C": ParamDef((d, G * N), ("embed", None)),
        "w_dt": ParamDef((d, H), ("embed", "heads")),
        "conv_x": ParamDef((W, di), (None, "mlp"), init="normal", scale=1.0),
        "conv_B": ParamDef((W, G * N), (None, None)),
        "conv_C": ParamDef((W, G * N), (None, None)),
        "A_log": ParamDef((H,), ("heads",), init="zeros"),
        "D": ParamDef((H,), ("heads",), init="ones"),
        "dt_bias": ParamDef((H,), ("heads",), init="zeros"),
        "norm": ParamDef((di,), ("mlp",), init="ones"),
        "w_out": ParamDef((di, d), ("mlp", "embed")),
    }


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv along S. x: (B, S, C); w: (W, C)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(W):
        out = out + xp[:, i : i + x.shape[1]].astype(jnp.float32) * w[i].astype(jnp.float32)
    return out.astype(x.dtype)


def _ssd_scan(
    xh: jax.Array,    # (B, S, H, P)  — conv'd, silu'd inputs
    dt: jax.Array,    # (B, S, H)     — softplus'd step sizes
    A: jax.Array,     # (H,)          — negative decay rates
    Bm: jax.Array,    # (B, S, G, N)
    Cm: jax.Array,    # (B, S, G, N)
    chunk: int,
    initial_state: jax.Array | None = None,   # (B, H, N, P)
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD. Returns (y (B,S,H,P), final_state (B,H,N,P))."""
    Bsz, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    R = H // G
    Q = min(chunk, S)
    assert S % Q == 0, f"seq {S} not divisible by chunk {Q}"
    nc = S // Q

    f32 = jnp.float32
    xc = xh.reshape(Bsz, nc, Q, H, P).astype(f32)
    dtc = dt.reshape(Bsz, nc, Q, H).astype(f32)
    Bc = Bm.reshape(Bsz, nc, Q, G, N).astype(f32)
    Cc = Cm.reshape(Bsz, nc, Q, G, N).astype(f32)

    dtx = dtc[..., None] * xc                                  # (B,nc,Q,H,P)
    log_a = A.astype(f32) * dtc                                # negative, (B,nc,Q,H)
    cum = jnp.cumsum(log_a, axis=2)                            # inclusive cumsum
    cum_last = cum[:, :, -1]                                   # (B,nc,H)

    # ---- intra-chunk (quadratic within Q) ----
    s = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc)               # (B,nc,G,Q,Q)
    s = jnp.repeat(s, R, axis=2)                               # (B,nc,H,Q,Q)
    decay = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # cum_i - cum_j (B,nc,Q,Q,H)
    decay = jnp.moveaxis(decay, -1, 2)                         # (B,nc,H,Q,Q)
    iq = jnp.arange(Q)
    causal = (iq[:, None] >= iq[None, :])[None, None, None]
    # mask *before* the exp: above the diagonal cum_i − cum_j > 0 grows with
    # the chunk length and overflows f32 at Q = 256, and masking the
    # product afterwards still sends 0·inf = NaN back through exp's gradient
    M = s * jnp.exp(jnp.where(causal, decay, -jnp.inf))
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", M, dtx)

    # ---- per-chunk outgoing state ----
    w_end = jnp.exp(cum_last[:, :, None, :] - cum)             # decay to chunk end (B,nc,Q,H)
    # state contribution: sum_j w_end_j * B_j ⊗ dtx_j → (B,nc,H,N,P)
    Bfull = jnp.repeat(Bc, R, axis=3)                          # (B,nc,Q,H,N)
    chunk_states = jnp.einsum("bcjhn,bcjhp,bcjh->bchnp", Bfull, dtx, w_end)

    # ---- inter-chunk recurrence (sequential scan over nc chunks) ----
    state0 = (
        initial_state.astype(f32)
        if initial_state is not None
        else jnp.zeros((Bsz, H, N, P), f32)
    )
    Cfull = jnp.repeat(Cc, R, axis=3)                          # (B,nc,Q,H,N)

    def body(state, inp):
        c_full, cum_c, cum_last_c, cs = inp
        # y_inter[i] = exp(cum_i) · C_i · state_prev
        w_in = jnp.exp(cum_c)                                  # (B,Q,H)
        y_int = jnp.einsum("bqhn,bhnp,bqh->bqhp", c_full, state, w_in)
        state_new = jnp.exp(cum_last_c)[..., None, None] * state + cs
        return state_new, y_int

    xs = (
        jnp.moveaxis(Cfull, 1, 0),        # (nc, B, Q, H, N)
        jnp.moveaxis(cum, 1, 0),          # (nc, B, Q, H)
        jnp.moveaxis(cum_last, 1, 0),     # (nc, B, H)
        jnp.moveaxis(chunk_states, 1, 0),  # (nc, B, H, N, P)
    )
    final_state, y_inter = jax.lax.scan(body, state0, xs)
    y_inter = jnp.moveaxis(y_inter, 0, 1).reshape(Bsz, nc, Q, H, P)

    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y.astype(xh.dtype), final_state


class MambaCache(NamedTuple):
    """Decode-time state: SSM state + conv tail (last W−1 inputs)."""

    state: jax.Array     # (B, H, N, P) f32
    conv_x: jax.Array    # (B, W-1, di)
    conv_B: jax.Array    # (B, W-1, G·N)
    conv_C: jax.Array    # (B, W-1, G·N)


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype) -> MambaCache:
    H, N, P = cfg.n_ssm_heads, cfg.ssm_state, cfg.ssm_head_dim
    W = cfg.ssm_conv_width
    G = cfg.ssm_groups
    return MambaCache(
        state=jnp.zeros((batch, H, N, P), jnp.float32),
        conv_x=jnp.zeros((batch, W - 1, cfg.d_inner_ssm), dtype),
        conv_B=jnp.zeros((batch, W - 1, G * N), dtype),
        conv_C=jnp.zeros((batch, W - 1, G * N), dtype),
    )


def _proj_zxbcdt(p: dict, x: jax.Array):
    z = jnp.einsum("bsd,df->bsf", x, p["w_z"])
    xr = jnp.einsum("bsd,df->bsf", x, p["w_x"])
    Br = jnp.einsum("bsd,df->bsf", x, p["w_B"])
    Cr = jnp.einsum("bsd,df->bsf", x, p["w_C"])
    dt = jnp.einsum("bsd,dh->bsh", x, p["w_dt"])
    return z, xr, Br, Cr, dt


def mamba_apply(
    p: dict, cfg: ModelConfig, x: jax.Array, return_state: bool = False,
):
    """Train/prefill SSD pass. x: (B, S, D) → (B, S, D) [, MambaCache]."""
    Bsz, S, _ = x.shape
    H, N, P, G = cfg.n_ssm_heads, cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_groups
    W = cfg.ssm_conv_width

    z, xr_raw, Br_raw, Cr_raw, dt = _proj_zxbcdt(p, x)
    xr = jax.nn.silu(_causal_conv(xr_raw, p["conv_x"]).astype(jnp.float32)).astype(x.dtype)
    Br = jax.nn.silu(_causal_conv(Br_raw, p["conv_B"]).astype(jnp.float32)).astype(x.dtype)
    Cr = jax.nn.silu(_causal_conv(Cr_raw, p["conv_C"]).astype(jnp.float32)).astype(x.dtype)

    xh = xr.reshape(Bsz, S, H, P)
    xh = shard_act(xh, "batch", None, "heads", None)
    Bm = Br.reshape(Bsz, S, G, N)
    Cm = Cr.reshape(Bsz, S, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    y, final_state = _ssd_scan(xh, dt, A, Bm, Cm, cfg.ssm_chunk)
    y = y + p["D"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(Bsz, S, H * P)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bsf,fd->bsd", y, p["w_out"])
    if return_state:
        def tail(raw):
            t = raw[:, -(W - 1):]
            pad = (W - 1) - t.shape[1]
            return jnp.pad(t, [(0, 0), (pad, 0), (0, 0)]) if pad else t
        cache = MambaCache(
            state=final_state,
            conv_x=tail(xr_raw), conv_B=tail(Br_raw), conv_C=tail(Cr_raw),
        )
        return out, cache
    return out


def mamba_decode_apply(
    p: dict, cfg: ModelConfig, x: jax.Array, cache: MambaCache,
) -> tuple[jax.Array, MambaCache]:
    """One-token recurrence. x: (B, 1, D)."""
    Bsz = x.shape[0]
    H, N, P, G = cfg.n_ssm_heads, cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_groups

    z, xr, Br, Cr, dt = _proj_zxbcdt(p, x)

    def step_conv(tail: jax.Array, new: jax.Array, w: jax.Array):
        """tail: (B, W-1, C); new: (B, 1, C) → (conv output (B, C), new tail)."""
        window = jnp.concatenate([tail, new.astype(tail.dtype)], axis=1)  # (B, W, C)
        out = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32), w.astype(jnp.float32))
        return out, window[:, 1:]

    cx, tail_x = step_conv(cache.conv_x, xr, p["conv_x"])
    cB, tail_B = step_conv(cache.conv_B, Br, p["conv_B"])
    cC, tail_C = step_conv(cache.conv_C, Cr, p["conv_C"])
    xh = jax.nn.silu(cx).reshape(Bsz, H, P)
    Bm = jax.nn.silu(cB).reshape(Bsz, G, N)
    Cm = jax.nn.silu(cC).reshape(Bsz, G, N)

    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))  # (B,H)
    a = jnp.exp(-jnp.exp(p["A_log"].astype(jnp.float32)) * dt1)     # (B,H)

    R = H // G
    Bfull = jnp.repeat(Bm, R, axis=1)                               # (B,H,N)
    Cfull = jnp.repeat(Cm, R, axis=1)
    dtx = dt1[..., None] * xh.astype(jnp.float32)                   # (B,H,P)
    state = a[..., None, None] * cache.state + Bfull[..., None] * dtx[:, :, None, :]
    y = jnp.einsum("bhn,bhnp->bhp", Cfull.astype(jnp.float32), state)
    y = y + p["D"].astype(jnp.float32)[None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(Bsz, 1, H * P).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bsf,fd->bsd", y, p["w_out"])
    return out, MambaCache(state=state, conv_x=tail_x, conv_B=tail_B, conv_C=tail_C)
