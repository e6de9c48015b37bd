"""Pallas TPU kernel: tiled worker-Gram matrix.

The master-side hot spot of ByzantineSGD (and of Krum, which the paper's
Table 1 costs at O(m²d)): G = X Xᵀ for X = (m, d) stacked worker vectors,
with d = |params| ≫ VMEM.  One MXU matmul per streamed strip, accumulated
into the resident (m, m) output — the shared layout of DESIGN.md §4.
Standalone form of the Gram terms; the guard's step-loop uses the fused
variant in :mod:`repro.kernels.fused_guard` instead (DESIGN.md §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# f32 contractions on the MXU: at the default precision the TPU rounds f32
# operands to bf16 (about 1e-3 relative error in a Gram); bf16 strips,
# upcast in VMEM, are exact either way
_F32_DOT = jax.lax.Precision.HIGHEST


def _gram_kernel(x_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)
    out_ref[...] += jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())), precision=_F32_DOT,
        preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("d_block", "interpret"))
def gram_pallas(x: jax.Array, d_block: int = 2048, interpret: bool = False) -> jax.Array:
    """(m, d) → (m, m) f32 Gram via the tiled kernel.

    The wrapper pads m up to the 8-sublane multiple and d up to d_block
    (zero padding is exact for a Gram matrix).
    """
    m, d = x.shape
    m_pad = (-m) % 8
    d_pad = (-d) % d_block
    if m_pad or d_pad:
        x = jnp.pad(x, ((0, m_pad), (0, d_pad)))
    mp, dp = x.shape

    out = pl.pallas_call(
        _gram_kernel,
        grid=(dp // d_block,),
        in_specs=[pl.BlockSpec((mp, d_block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((mp, mp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, mp), jnp.float32),
        interpret=interpret,
    )(x)
    return out[:m, :m]
