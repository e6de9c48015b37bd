"""Plain decoder-only transformer with grouped-query attention and a SwiGLU
MLP (the InternLM2 block, arXiv:2403.17297), f32.

Per layer (pre-norm residual):

    h  = rmsnorm(x) * norm1
    q, k, v = h Wq, h Wk, h Wv          H query heads, KV key/value heads
    q, k = rope(q), rope(k)             pairs (2i, 2i+1), theta^(-2i/hd)
    o  = softmax(q k^T / sqrt(hd) + causal mask) v    head h reads KV head h // (H/KV)
    x += o Wo
    h  = rmsnorm(x) * norm2
    x += (silu(h Wgate) * (h Wup)) Wdown

then a final RMS norm, the LM head and the mean next-token cross entropy.
The whole (S, S) score matrix is formed and soft-maxed at once: no online
softmax, no key blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference.common import Leaf, lm_defs, rms_norm
from perfbench.reference.mamba2 import head_loss


def defs(m: dict) -> dict:
    d = m["d_model"]
    H, KV, hd, F = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    block = {
        "norm1": Leaf((d,), "ones"), "norm2": Leaf((d,), "ones"),
        "mixer": {"wq": Leaf((d, H, hd)), "wk": Leaf((d, KV, hd)),
                  "wv": Leaf((d, KV, hd)), "wo": Leaf((H, hd, d))},
        "ff": {"gate": Leaf((d, F)), "up": Leaf((d, F)), "down": Leaf((F, d))},
    }
    return lm_defs(m, block)


def rope(x, theta: float):
    """Rotate each pair (x_2i, x_2i+1) at position t by t * theta^(-2i/hd).
    x: (b, S, heads, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _attention(m: dict, mm, h, p):
    b, S, _ = h.shape
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = rope(mm("bsd,dhk->bshk", h, p["wq"]), m["rope_theta"])
    k = rope(mm("bsd,dhk->bshk", h, p["wk"]), m["rope_theta"])
    v = mm("bsd,dhk->bshk", h, p["wv"])
    q = q.reshape(b, S, KV, H // KV, hd)
    s = mm("bqkrh,bckh->bkrqc", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm("bkrqc,bckh->bqkrh", a, v).reshape(b, S, H, hd)
    return mm("bshk,hkd->bsd", o, p["wo"])


def _layer(m: dict, mm, x, p):
    eps = m["norm_eps"]
    x = x + _attention(m, mm, rms_norm(x, p["norm1"], eps), p["mixer"])
    h = rms_norm(x, p["norm2"], eps)
    ff = p["ff"]
    g = jax.nn.silu(mm("bsd,df->bsf", h, ff["gate"]))
    return x + mm("bsf,fd->bsd", g * mm("bsd,df->bsf", h, ff["up"]), ff["down"])


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
    forward), without recomputation: 2 per weight of the projections, the
    MLP and the LM head, plus causal attention, whose query at position t
    reads t + 1 keys for the scores and as many values."""
    d, V, L = m["d_model"], m["vocab_size"], m["n_layers"]
    H, KV, hd, F = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    matmul = L * (d * (H + 2 * KV) * hd + H * hd * d + 3 * d * F) + d * V
    mean_keys = (seq_len + 1) / 2
    attn = L * 2 * (2 * H * hd * mean_keys)
    return 3.0 * (2.0 * matmul + attn)
