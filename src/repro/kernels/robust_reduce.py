"""Pallas TPU kernels: coordinate-wise robust reductions over the worker axis.

The Yin et al. baseline (Median-GD / trimmed-mean-GD) and the paper's
filtered mean are all (m, d) → (d,) reductions: the strip-streaming
layout of DESIGN.md §4 with a (d_blk,) output strip per grid step.  The
reduction over m is a sorting network (odd-even min/max rounds) for the
order statistics and a masked dot for the filtered mean — no
(m, d)-sized temporaries (which the naive ``jnp.sort(axis=0)`` would
materialize), so the stream runs at HBM bandwidth.

Input strips stream in their storage dtype and are upcast to f32 in
VMEM (exact for bf16), so feeding bf16 worker data — the guard's
``stats_dtype`` axis, DESIGN.md §5 — halves the read traffic while the
reduction itself always accumulates and returns f32.
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


def _sorted_over_workers(x: jax.Array) -> jax.Array:
    """Bitonic-style full sort over axis 0 (m is small and static): odd-even
    transposition network with m rounds of elementwise min/max — vectorizes
    over the d_blk lane dimension, no data-dependent control flow."""
    m = x.shape[0]
    rows = [x[i] for i in range(m)]
    for rnd in range(m):
        start = rnd % 2
        for i in range(start, m - 1, 2):
            lo = jnp.minimum(rows[i], rows[i + 1])
            hi = jnp.maximum(rows[i], rows[i + 1])
            rows[i], rows[i + 1] = lo, hi
    return jnp.stack(rows, axis=0)


def _median_kernel(x_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    s = _sorted_over_workers(x)
    m = x.shape[0]
    if m % 2:
        out_ref[...] = s[m // 2]
    else:
        out_ref[...] = 0.5 * (s[m // 2 - 1] + s[m // 2])


def _trimmed_mean_kernel(x_ref, out_ref, *, n_trim: int):
    x = x_ref[...].astype(jnp.float32)
    s = _sorted_over_workers(x)
    m = x.shape[0]
    out_ref[...] = jnp.mean(s[n_trim : m - n_trim], axis=0)


def _filtered_mean_kernel(x_ref, mask_ref, out_ref, *, denom: float,
                          sanitize: bool = False):
    x = x_ref[...].astype(jnp.float32)
    if sanitize:
        # static gate (DESIGN.md §15): zeroed-weight rows must not poison
        # the dot — 0 × Inf = NaN — so quarantined rows are zeroed in VMEM
        # before the reduction; off-state kernel body is unchanged
        x = jnp.where(jnp.isfinite(x), x, 0.0)
    w = mask_ref[...].astype(jnp.float32) / denom
    # (1, m) @ (m, d_blk): Mosaic has no 1-D contraction
    out_ref[...] = jnp.dot(w[None, :], x, precision=_F32_DOT,
                           preferred_element_type=jnp.float32)[0]


def _reduce_call(kernel, x, extra_inputs=(), extra_specs=(), d_block=4096,
                 interpret=False):
    m, d = x.shape
    d_pad = (-d) % d_block
    if d_pad:
        x = jnp.pad(x, ((0, 0), (0, d_pad)))
    dp = x.shape[1]
    out = pl.pallas_call(
        kernel,
        grid=(dp // d_block,),
        in_specs=[pl.BlockSpec((m, d_block), lambda i: (0, i)), *extra_specs],
        out_specs=pl.BlockSpec((d_block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((dp,), jnp.float32),
        interpret=interpret,
    )(x, *extra_inputs)
    return out[:d]


@functools.partial(jax.jit, static_argnames=("d_block", "interpret"))
def coordinate_median_pallas(x: jax.Array, d_block: int = 4096,
                             interpret: bool = False) -> jax.Array:
    """(m, d) → (d,) coordinate-wise median."""
    return _reduce_call(_median_kernel, x, d_block=d_block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_trim", "d_block", "interpret"))
def trimmed_mean_pallas(x: jax.Array, n_trim: int, d_block: int = 4096,
                        interpret: bool = False) -> jax.Array:
    """(m, d) → (d,) coordinate-wise n_trim-trimmed mean."""
    if 2 * n_trim >= x.shape[0]:
        raise ValueError("trim exceeds worker count")
    return _reduce_call(
        functools.partial(_trimmed_mean_kernel, n_trim=n_trim),
        x, d_block=d_block, interpret=interpret,
    )


@functools.partial(jax.jit,
                   static_argnames=("denom", "d_block", "interpret", "sanitize"))
def filtered_mean_pallas(x: jax.Array, mask: jax.Array, denom: float,
                         d_block: int = 4096, interpret: bool = False,
                         sanitize: bool = False) -> jax.Array:
    """(m, d), (m,) → (d,): the paper's ξ_k = Σ_{i∈good_k} x_i / denom,
    fused mask-and-reduce (never materializes the masked copy).
    ``sanitize=True`` zeroes non-finite entries in VMEM first, so a
    quarantined (zero-weight) NaN/Inf row cannot poison the dot."""
    m = x.shape[0]
    mask_spec = pl.BlockSpec((m,), lambda i: (0,))
    return _reduce_call(
        functools.partial(_filtered_mean_kernel, denom=denom, sanitize=sanitize),
        x, extra_inputs=(mask.astype(jnp.float32),), extra_specs=(mask_spec,),
        d_block=d_block, interpret=interpret,
    )
