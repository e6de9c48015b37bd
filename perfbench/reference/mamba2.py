"""Plain Mamba2 language model (arXiv:2405.21060), f32, with the SSD mixer
written as its sequential recurrence.

Per layer (pre-norm residual, no MLP):

    h      = rmsnorm(x) * norm1
    z, x', B, C, dt = h W_z, h W_x, h W_B, h W_C, h W_dt
    x', B, C  = silu(causal depthwise conv_4(.))
    dt     = softplus(dt + dt_bias),   a_t = exp(-exp(A_log) * dt_t)
    S_t    = a_t S_{t-1} + B_t (dt_t x'_t)^T        per head, state N x P
    y_t    = C_t S_t + D x'_t
    x     += rmsnorm(y * silu(z)) * norm  W_out

then a final RMS norm, the LM head and the mean next-token cross entropy.
One token at a time, in f32: no chunked SSD, no kernel.  The time axis is
cut into blocks whose states are recomputed in the backward pass, so the
gradient fits the chip; that changes the memory, not the arithmetic.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.reference.common import Leaf, cross_entropy, lm_defs, rms_norm

TIME_BLOCK = 64      # recurrence steps between saved states
UNROLL = 16          # recurrence steps per loop iteration
LOSS_BLOCK = 512     # tokens per block of the cross entropy


def _widths(m: dict):
    di = m["ssm_expand"] * m["d_model"]
    return di, di // m["ssm_head_dim"], m["ssm_groups"] * m["ssm_state"]


def defs(m: dict) -> dict:
    d = m["d_model"]
    di, H, GN = _widths(m)
    W = m["ssm_conv_width"]
    mixer = {
        "w_z": Leaf((d, di)), "w_x": Leaf((d, di)),
        "w_B": Leaf((d, GN)), "w_C": Leaf((d, GN)), "w_dt": Leaf((d, H)),
        "conv_x": Leaf((W, di)), "conv_B": Leaf((W, GN)),
        "conv_C": Leaf((W, GN)),
        "A_log": Leaf((H,), "zeros"), "D": Leaf((H,), "ones"),
        "dt_bias": Leaf((H,), "zeros"), "norm": Leaf((di,), "ones"),
        "w_out": Leaf((di, d)),
    }
    block = {"norm1": Leaf((d,), "ones"), "norm2": Leaf((d,), "ones"),
             "mixer": mixer, "ff": {}}
    return lm_defs(m, block)


def _conv(x, w):
    """Causal depthwise conv: out_t = sum_i w_i x_{t-(W-1)+i}."""
    W, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(xp[:, i:i + S] * w[i] for i in range(W))


def _recurrence(xh, dt, A, Bm, Cm, mm):
    """y_t = C_t S_t, S_t = exp(A dt_t) S_{t-1} + B_t (dt_t x_t)^T.
    xh (b, S, H, P), dt (b, S, H), Bm/Cm (b, S, G, N) -> (b, S, H, P)."""
    b, S, H, P = xh.shape
    R = H // Bm.shape[2]
    Bh = jnp.repeat(Bm, R, axis=2)
    Ch = jnp.repeat(Cm, R, axis=2)
    a = jnp.exp(A * dt)
    u = dt[..., None] * xh
    T = math.gcd(S, TIME_BLOCK)
    seq = [jnp.moveaxis(v, 1, 0).reshape(S // T, T, *v.shape[:1], *v.shape[2:])
           for v in (a, Bh, Ch, u)]

    def step(state, inp):
        at, bt, ct, ut = inp
        state = at[..., None, None] * state + bt[..., :, None] * ut[..., None, :]
        return state, mm("bhn,bhnp->bhp", ct, state)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp, unroll=UNROLL)

    state0 = jnp.zeros((b, H, Bm.shape[3], P), jnp.float32)
    _, y = jax.lax.scan(block, state0, seq)
    return jnp.moveaxis(y.reshape(S, b, H, P), 0, 1)


def _layer(m: dict, mm, x, p):
    eps = m["norm_eps"]
    b, S, _ = x.shape
    di, H, _ = _widths(m)
    G, N, P = m["ssm_groups"], m["ssm_state"], m["ssm_head_dim"]
    mix = p["mixer"]
    h = rms_norm(x, p["norm1"], eps)
    z = mm("bsd,df->bsf", h, mix["w_z"])
    xr = jax.nn.silu(_conv(mm("bsd,df->bsf", h, mix["w_x"]), mix["conv_x"]))
    Bm = jax.nn.silu(_conv(mm("bsd,df->bsf", h, mix["w_B"]), mix["conv_B"]))
    Cm = jax.nn.silu(_conv(mm("bsd,df->bsf", h, mix["w_C"]), mix["conv_C"]))
    dt = jax.nn.softplus(mm("bsd,dh->bsh", h, mix["w_dt"]) + mix["dt_bias"])
    xh = xr.reshape(b, S, H, P)
    y = _recurrence(xh, dt, -jnp.exp(mix["A_log"]),
                    Bm.reshape(b, S, G, N), Cm.reshape(b, S, G, N), mm)
    y = (y + mix["D"][:, None] * xh).reshape(b, S, di)
    y = rms_norm(y * jax.nn.silu(z), mix["norm"], eps)
    return x + mm("bsf,fd->bsd", y, mix["w_out"])


def head_loss(m: dict, mm, params, x, labels):
    """Final norm, LM head (the embedding's transpose where the two are
    tied) and mean cross entropy, in blocks of tokens."""
    head = params["embed"].T if m["tie_embeddings"] else params["lm_head"]
    x = rms_norm(x, params["final_norm"], m["norm_eps"])
    b, S, d = x.shape
    n = S // math.gcd(S, LOSS_BLOCK)
    xs = (jnp.moveaxis(x.reshape(b, n, S // n, d), 1, 0),
          jnp.moveaxis(labels.reshape(b, n, S // n), 1, 0))

    @jax.checkpoint
    def block(tot, inp):
        hb, lb = inp
        logits = mm("bsd,dv->bsv", hb, head)
        return tot + cross_entropy(logits, lb), None

    tot, _ = jax.lax.scan(block, jnp.float32(0.0), xs)
    return tot / n


def loss(m: dict, mm, params, tokens, labels):
    """Mean next-token cross entropy of one worker's rows (b, S)."""
    x = params["embed"][tokens]

    @jax.checkpoint
    def body(x, p):
        return _layer(m, mm, x, p), None

    x, _ = jax.lax.scan(body, x, params["groups"][0])
    return head_loss(m, mm, params, x, labels)


def flops_per_token(m: dict, seq_len: int) -> float:
    """Operations of the forward and backward passes per token (3x the
    forward), without recomputation.  Matmuls: 2 per weight of the
    projections and the LM head (the embedding is a gather).  SSD, as the
    chunked algorithm of the paper computes it with chunk Q: the causal
    half of the Q x Q scores C B^T and of their product with x, the chunk
    state B^T x and its read-out C S."""
    d, V, L = m["d_model"], m["vocab_size"], m["n_layers"]
    di, H, GN = _widths(m)
    N, P = m["ssm_state"], m["ssm_head_dim"]
    Q = min(m["ssm_chunk"], seq_len)
    matmul = L * (d * (2 * di + 2 * GN + H) + di * d) + d * V
    half = (Q + 1) / 2
    ssd = L * (2 * half * GN + 2 * half * H * P + 4 * H * N * P)
    return 3.0 * (2.0 * matmul + ssd)
