"""Pallas TPU kernel: fused sign-flip + strided-fold CountSketch.

The sketch-mode guard (DESIGN.md §3) compresses each worker's (huge)
gradient into k buckets: s_c = Σ_{i ≡ c (mod k)} σ(i)·x_i with hashed
signs.  Layout is the shared strip convention of DESIGN.md §4 (with
d_blk constrained to a multiple of k and an (m, k) resident output); the
twist is that the sign pattern is *generated inside the kernel* from the
global coordinate index (iota + block offset → multiplicative hash) —
zero bytes of hash state ever touch HBM, so the stream runs at pure read
bandwidth.  Strips stream in their storage dtype and are upcast to f32
in VMEM (exact for bf16), so bf16 inputs — the ``stats_dtype`` axis of
DESIGN.md §5 — halve the read traffic; the (m, k) sketch accumulates
and returns f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sign_hash(idx: jax.Array, salt: int) -> jax.Array:
    h = (idx + jnp.uint32((salt * 0x9E3779B9 + 1) & 0xFFFFFFFF)) * jnp.uint32(2654435761)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    return 1.0 - 2.0 * (h & 1).astype(jnp.int32).astype(jnp.float32)


def _countsketch_kernel(x_ref, out_ref, *, k: int, d_block: int, salt: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    m = x_ref.shape[0]
    x = x_ref[...].astype(jnp.float32)                     # (m, d_blk)
    base = (i * d_block).astype(jnp.uint32) if hasattr(i, "astype") else jnp.uint32(i * d_block)
    idx = jax.lax.iota(jnp.uint32, d_block) + base         # global coordinate ids
    sign = _sign_hash(idx, salt)
    folded = (x * sign[None, :]).reshape(m, d_block // k, k)
    out_ref[...] += jnp.sum(folded, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "salt", "d_block", "interpret"))
def countsketch_pallas(
    x: jax.Array, k: int, salt: int = 0, d_block: int = 8192, interpret: bool = False,
) -> jax.Array:
    """(m, d) → (m, k) strided-fold CountSketch, matching
    :func:`repro.kernels.ref.countsketch_ref` bit-for-bit in f32."""
    m, d = x.shape
    d_block = max(k, (d_block // k) * k)
    d_pad = (-d) % d_block
    if d_pad:
        x = jnp.pad(x, ((0, 0), (0, d_pad)))
    dp = x.shape[1]
    return pl.pallas_call(
        functools.partial(_countsketch_kernel, k=k, d_block=d_block, salt=salt),
        grid=(dp // d_block,),
        in_specs=[pl.BlockSpec((m, d_block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((m, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        interpret=interpret,
    )(x)
