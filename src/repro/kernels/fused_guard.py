"""Pallas TPU kernel: one-pass fused guard-statistics pipeline.

Algorithm 1's per-iteration filter needs four quantities that each touch
the full (m, d) worker data: the fresh-gradient Gram ``g gᵀ``, the cross
Gram ``B gᵀ`` (feeding the incremental B-martingale Gram, see DESIGN.md
§5), the A-martingale increments ``g · (x_k − x_1)``, and the updated
martingale matrix ``B + g``.  Computed separately (as the dense reference
in :mod:`repro.core.byzantine_sgd` does) that is three independent sweeps
over HBM; this kernel produces all four in a *single* grid pass — every
(m, d_blk) strip of ``grads`` and ``B`` is read exactly once and the new
``B`` strip is written in place of a separate accumulation pass.

Layout is the shared strip convention of :mod:`repro.kernels` (m padded
to the next 8-sublane multiple) with two resident (m, m) accumulators
and one resident (m,) accumulator alongside the streamed ``B`` output
strip.  With ``e = element bytes`` of the streamed strips, VMEM per step
= 2·m·d_blk·e (g + B in) + m·d_blk·e (B out) + 2·m²·4 + m·4 bytes
≈ 0.8 MB at m=32, d_blk=2048, e=4 — comfortably inside the
double-buffered ~16 MB/core budget (and half that under bf16 strips).

Roofline (DESIGN.md §5): HBM traffic drops from 6·m·d·e bytes per guard
step (dense: g read 3×, B read 2×, B written 1×) to 3·m·d·e (g read 1×,
B read 1×, B written 1×) — a 2× reduction by the pass-count model in
``repro.roofline.guard_cost``, recorded alongside measured wall-clock by
``benchmarks/bench_filtering.py``.

**Mixed-precision statistics** (``SolverConfig.stats_dtype``): the
streamed strips may be bf16 — ``grads``/``B`` are read in their storage
dtype and the new ``B`` strip is written back in ``B.dtype``, halving
``e`` and therefore the whole sweep's HBM traffic.  Every accumulator
(both Grams, the A-increments) stays f32: inputs are upcast *in VMEM*
(bf16 → f32 is exact), so the contraction numerics are identical to an
f32 sweep over the same bf16-rounded values and the only rounding the
dtype axis introduces is the per-step ``B_new`` store.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gradgen import GEN_NPARAMS, gen_worker_rows

# f32 contractions on the MXU: at the default precision the TPU rounds f32
# operands to bf16 (about 1e-3 relative error in a Gram); bf16 strips,
# upcast in VMEM, are exact either way
_F32_DOT = jax.lax.Precision.HIGHEST


def _fused_guard_kernel(g_ref, b_ref, delta_ref,
                        gram_g_ref, cross_ref, a_inc_ref, b_new_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        gram_g_ref[...] = jnp.zeros_like(gram_g_ref)
        cross_ref[...] = jnp.zeros_like(cross_ref)
        a_inc_ref[...] = jnp.zeros_like(a_inc_ref)

    g = g_ref[...].astype(jnp.float32)        # (m, d_blk)
    b = b_ref[...].astype(jnp.float32)        # (m, d_blk)
    dlt = delta_ref[...].astype(jnp.float32)  # (d_blk,)

    contract = (((1,), (1,)), ((), ()))
    gram_g_ref[...] += jax.lax.dot_general(
        g, g, contract, precision=_F32_DOT,
        preferred_element_type=jnp.float32
    )
    cross_ref[...] += jax.lax.dot_general(     # ⟨B_i, g_j⟩ — pre-update B
        b, g, contract, precision=_F32_DOT,
        preferred_element_type=jnp.float32
    )
    a_inc_ref[...] += jnp.sum(g * dlt[None, :], axis=1)
    # f32 add, rounded once on the store when the B strips are bf16
    b_new_ref[...] = (b + g).astype(b_new_ref.dtype)


def _fused_guard_sanitize_kernel(g_ref, b_ref, delta_ref,
                                 gram_g_ref, cross_ref, a_inc_ref, nf_ref,
                                 b_new_ref):
    """Sanitizing variant (DESIGN.md §15): identical products, but NaN/Inf
    gradient entries are zeroed *in VMEM* before any contraction and the
    per-row non-finite count accumulates across strips — the non-finite
    check rides the one HBM sweep instead of costing its own (m, d) pass.
    A separate kernel body (not a flag on the base kernel) so the off-state
    pallas_call is byte-identical to the pre-sanitize build."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        gram_g_ref[...] = jnp.zeros_like(gram_g_ref)
        cross_ref[...] = jnp.zeros_like(cross_ref)
        a_inc_ref[...] = jnp.zeros_like(a_inc_ref)
        nf_ref[...] = jnp.zeros_like(nf_ref)

    g = g_ref[...].astype(jnp.float32)        # (m, d_blk)
    fin = jnp.isfinite(g)
    nf_ref[...] += jnp.sum((~fin).astype(jnp.int32), axis=1)
    g = jnp.where(fin, g, 0.0)
    b = b_ref[...].astype(jnp.float32)        # (m, d_blk)
    dlt = delta_ref[...].astype(jnp.float32)  # (d_blk,)

    contract = (((1,), (1,)), ((), ()))
    gram_g_ref[...] += jax.lax.dot_general(
        g, g, contract, precision=_F32_DOT,
        preferred_element_type=jnp.float32
    )
    cross_ref[...] += jax.lax.dot_general(
        b, g, contract, precision=_F32_DOT,
        preferred_element_type=jnp.float32
    )
    a_inc_ref[...] += jnp.sum(g * dlt[None, :], axis=1)
    # B accumulates the *sanitized* gradient: the martingale stays finite
    # forever (one NaN entry would otherwise poison B_i for the whole run)
    b_new_ref[...] = (b + g).astype(b_new_ref.dtype)


@functools.partial(jax.jit, static_argnames=("d_block", "interpret", "sanitize"))
def fused_guard_pallas(
    grads: jax.Array,   # (m, d) fresh per-worker gradients
    B: jax.Array,       # (m, d) martingale matrix B_{k-1}
    delta: jax.Array,   # (d,)   x_k − x_1
    d_block: int = 2048,
    interpret: bool = False,
    sanitize: bool = False,
) -> tuple[jax.Array, ...]:
    """One-pass guard statistics: ``(gram_g, cross, a_inc, B_new)`` with

    * ``gram_g[i, j] = ⟨∇_i, ∇_j⟩``            (m, m) f32
    * ``cross[i, j]  = ⟨B_{k-1,i}, ∇_j⟩``      (m, m) f32
    * ``a_inc[i]     = ⟨∇_i, x_k − x_1⟩``      (m,)   f32
    * ``B_new        = B_{k-1} + ∇``           (m, d) in ``B.dtype``

    matching :func:`repro.kernels.ref.fused_guard_ref`.  ``B.dtype`` is
    the statistics storage dtype (f32 or bf16 — the ``stats_dtype`` axis);
    the f32 sum is rounded once on the ``B_new`` store.  The caller folds
    ``cross`` into the incremental Gram ``G_B^k = G_B^{k-1} + cross +
    crossᵀ + gram_g``.  Padding (m → ×8, d → ×d_block) is with zeros,
    which is exact for all four outputs.

    ``sanitize=True`` (static, DESIGN.md §15) zeroes NaN/Inf gradient
    entries in VMEM before every product and appends a fifth output
    ``nf`` — the (m,) int32 per-row non-finite entry count — so the
    quarantine decision costs no extra HBM pass; matches
    :func:`repro.kernels.ref.fused_guard_sanitize_ref`.
    """
    m, d = grads.shape
    if B.shape != (m, d):
        raise ValueError(f"B shape {B.shape} != grads shape {(m, d)}")
    m_pad = (-m) % 8
    d_pad = (-d) % d_block
    if m_pad or d_pad:
        grads = jnp.pad(grads, ((0, m_pad), (0, d_pad)))
        B = jnp.pad(B, ((0, m_pad), (0, d_pad)))
    if d_pad:
        delta = jnp.pad(delta, (0, d_pad))
    mp, dp = grads.shape

    out_specs = [
        pl.BlockSpec((mp, mp), lambda i: (0, 0)),
        pl.BlockSpec((mp, mp), lambda i: (0, 0)),
        pl.BlockSpec((mp,), lambda i: (0,)),
        pl.BlockSpec((mp, d_block), lambda i: (0, i)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((mp, mp), jnp.float32),
        jax.ShapeDtypeStruct((mp, mp), jnp.float32),
        jax.ShapeDtypeStruct((mp,), jnp.float32),
        jax.ShapeDtypeStruct((mp, dp), B.dtype),
    ]
    kernel = _fused_guard_kernel
    if sanitize:
        kernel = _fused_guard_sanitize_kernel
        # nf accumulator sits before the streamed B strip so the resident
        # accumulators stay contiguous in the output list
        out_specs.insert(3, pl.BlockSpec((mp,), lambda i: (0,)))
        out_shape.insert(3, jax.ShapeDtypeStruct((mp,), jnp.int32))

    # named scope (DESIGN.md §12 span convention): XLA profiles attribute
    # the sweep's device time to guard/pallas_fused_guard instead of an
    # anonymous custom-call — metadata only, no ops
    with jax.named_scope("guard/pallas_fused_guard"):
        outs = pl.pallas_call(
            kernel,
            grid=(dp // d_block,),
            in_specs=[
                pl.BlockSpec((mp, d_block), lambda i: (0, i)),
                pl.BlockSpec((mp, d_block), lambda i: (0, i)),
                pl.BlockSpec((d_block,), lambda i: (i,)),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
        )(grads, B, delta)
    if sanitize:
        gram_g, cross, a_inc, nf, b_new = outs
        return (gram_g[:m, :m], cross[:m, :m], a_inc[:m], b_new[:m, :d],
                nf[:m])
    gram_g, cross, a_inc, b_new = outs
    return gram_g[:m, :m], cross[:m, :m], a_inc[:m], b_new[:m, :d]


# ---------------------------------------------------------------------------
# generating variants (DESIGN.md §14): the gradient strips are regenerated
# in-kernel from (key, coordinate) counters instead of being read from HBM
# ---------------------------------------------------------------------------


def _gen_strip(x_ref, h_ref, xs_ref, hd_ref, keys_ref, skew_ref, slot_ref,
               params_ref, *, d_block, d):
    """Shared kernel prologue: regenerate this grid step's attacked worker
    strip (mp, d_blk) f32 via :func:`repro.kernels.gradgen.gen_worker_rows`."""
    i = pl.program_id(0)
    # TPU iota must be rank ≥ 2: a (1, d_blk) row of global coordinates
    j = (i * d_block + jax.lax.broadcasted_iota(jnp.int32, (1, d_block), 1)
         ).astype(jnp.uint32)
    return gen_worker_rows(
        x_ref[...].astype(jnp.float32),
        h_ref[...].astype(jnp.float32),
        xs_ref[...].astype(jnp.float32),
        hd_ref[...].astype(jnp.float32),
        keys_ref[...],
        skew_ref[...].astype(jnp.float32),
        slot_ref[...],
        params_ref[...].astype(jnp.float32),
        j, d,
    )


def _fused_guard_gen_kernel(b_ref, delta_ref, x_ref, h_ref, xs_ref, hd_ref,
                            keys_ref, skew_ref, slot_ref, params_ref,
                            gram_g_ref, cross_ref, a_inc_ref, b_new_ref,
                            *, d_block, d):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        gram_g_ref[...] = jnp.zeros_like(gram_g_ref)
        cross_ref[...] = jnp.zeros_like(cross_ref)
        a_inc_ref[...] = jnp.zeros_like(a_inc_ref)

    rows = _gen_strip(x_ref, h_ref, xs_ref, hd_ref, keys_ref, skew_ref,
                      slot_ref, params_ref, d_block=d_block, d=d)
    # mirror the materializing path's storage rounding: the host casts the
    # attacked grads to stats_dtype before the sweep, which then upcasts —
    # round-trip through B's dtype so bf16 statistics stay pinned to it
    g = rows.astype(b_new_ref.dtype).astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    dlt = delta_ref[...].astype(jnp.float32)

    contract = (((1,), (1,)), ((), ()))
    gram_g_ref[...] += jax.lax.dot_general(
        g, g, contract, precision=_F32_DOT,
        preferred_element_type=jnp.float32
    )
    cross_ref[...] += jax.lax.dot_general(
        b, g, contract, precision=_F32_DOT,
        preferred_element_type=jnp.float32
    )
    a_inc_ref[...] += jnp.sum(g * dlt[None, :], axis=1)
    b_new_ref[...] = (b + g).astype(b_new_ref.dtype)


@functools.partial(jax.jit, static_argnames=("d_block", "interpret"))
def fused_guard_gen_pallas(
    B: jax.Array,          # (m, d) martingale matrix B_{k-1}
    delta: jax.Array,      # (d,)   x_k − x_1
    x: jax.Array,          # (d,)   current iterate
    h: jax.Array,          # (d,)   diagonal curvature
    x_star: jax.Array,     # (d,)   optimum
    het_dir: jax.Array,    # (d,)   rank-1 skew direction (zeros if iid)
    keys: jax.Array,       # (m, 2) uint32 worker key words
    skewsign: jax.Array,   # (m,)   f32 skew·sign per worker
    slot: jax.Array,       # (m,)   int32 attack slot per worker
    params: jax.Array,     # (GEN_NPARAMS,) f32 attack parameters
    d_block: int = 2048,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """:func:`fused_guard_pallas` with the ``grads`` operand *generated*
    in-kernel — same four outputs, but the (m, d) gradient batch never
    exists in HBM, so the sweep reads/writes only the two B strips:
    2·m·d·e bytes vs the materializing kernel's 3·m·d·e (plus the batch's
    own producer traffic).  Padded worker rows carry ``slot = −1`` and
    padded coordinates are masked against the static true ``d`` inside the
    generator, since generated values (unlike zero-padded inputs) are
    nonzero in the padding."""
    m, d = B.shape
    if keys.shape != (m, 2):
        raise ValueError(f"keys shape {keys.shape} != {(m, 2)}")
    if params.shape != (GEN_NPARAMS,):
        raise ValueError(f"params shape {params.shape} != {(GEN_NPARAMS,)}")
    m_pad = (-m) % 8
    d_pad = (-d) % d_block
    if m_pad:
        B = jnp.pad(B, ((0, m_pad), (0, 0)))
        keys = jnp.pad(keys, ((0, m_pad), (0, 0)))
        skewsign = jnp.pad(skewsign, (0, m_pad))
        slot = jnp.pad(slot, (0, m_pad), constant_values=-1)
    if d_pad:
        B = jnp.pad(B, ((0, 0), (0, d_pad)))
        delta = jnp.pad(delta, (0, d_pad))
        x = jnp.pad(x, (0, d_pad))
        h = jnp.pad(h, (0, d_pad))
        x_star = jnp.pad(x_star, (0, d_pad))
        het_dir = jnp.pad(het_dir, (0, d_pad))
    mp, dp = B.shape

    kernel = functools.partial(_fused_guard_gen_kernel, d_block=d_block, d=d)
    with jax.named_scope("guard/pallas_fused_guard_gen"):
        gram_g, cross, a_inc, b_new = pl.pallas_call(
            kernel,
            grid=(dp // d_block,),
            in_specs=[
                pl.BlockSpec((mp, d_block), lambda i: (0, i)),   # B
                pl.BlockSpec((d_block,), lambda i: (i,)),        # delta
                pl.BlockSpec((d_block,), lambda i: (i,)),        # x
                pl.BlockSpec((d_block,), lambda i: (i,)),        # h
                pl.BlockSpec((d_block,), lambda i: (i,)),        # x_star
                pl.BlockSpec((d_block,), lambda i: (i,)),        # het_dir
                pl.BlockSpec((mp, 2), lambda i: (0, 0)),         # keys
                pl.BlockSpec((mp,), lambda i: (0,)),             # skewsign
                pl.BlockSpec((mp,), lambda i: (0,)),             # slot
                pl.BlockSpec((GEN_NPARAMS,), lambda i: (0,)),    # params
            ],
            out_specs=[
                pl.BlockSpec((mp, mp), lambda i: (0, 0)),
                pl.BlockSpec((mp, mp), lambda i: (0, 0)),
                pl.BlockSpec((mp,), lambda i: (0,)),
                pl.BlockSpec((mp, d_block), lambda i: (0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((mp, mp), jnp.float32),
                jax.ShapeDtypeStruct((mp, mp), jnp.float32),
                jax.ShapeDtypeStruct((mp,), jnp.float32),
                jax.ShapeDtypeStruct((mp, dp), B.dtype),
            ],
            interpret=interpret,
        )(B, delta, x, h, x_star, het_dir, keys, skewsign, slot, params)
    return gram_g[:m, :m], cross[:m, :m], a_inc[:m], b_new[:m, :d]


def _gen_xi_kernel(wxi_ref, wbyz_ref, x_ref, h_ref, xs_ref, hd_ref,
                   keys_ref, skew_ref, slot_ref, params_ref,
                   xi_ref, byz_ref, *, d_block, d, stats_dtype):
    rows = _gen_strip(x_ref, h_ref, xs_ref, hd_ref, keys_ref, skew_ref,
                      slot_ref, params_ref, d_block=d_block, d=d)
    # ξ consumes the stats-rounded strips (what the materializing guard's
    # filtered_mean sees); the adversary's byz-row feedback consumes the
    # raw f32 rows (what the host adversary.update_state sees)
    gs = rows.astype(stats_dtype).astype(jnp.float32)
    w = wxi_ref[...].astype(jnp.float32)
    # (1, m) @ (m, d_blk): Mosaic has no 1-D contraction
    xi_ref[...] = jnp.dot(w[None, :], gs, precision=_F32_DOT,
                          preferred_element_type=jnp.float32)[0]
    byz_ref[...] = jnp.sum(rows * wbyz_ref[...][:, None], axis=0)


@functools.partial(jax.jit,
                   static_argnames=("d_block", "interpret", "stats_dtype"))
def gen_xi_pallas(
    w_xi: jax.Array,       # (m,) f32 aggregation weights (contrib / denom)
    w_byz: jax.Array,      # (m,) f32 Byzantine mask weights
    x: jax.Array,          # (d,)
    h: jax.Array,          # (d,)
    x_star: jax.Array,     # (d,)
    het_dir: jax.Array,    # (d,)
    keys: jax.Array,       # (m, 2) uint32
    skewsign: jax.Array,   # (m,) f32
    slot: jax.Array,       # (m,) int32
    params: jax.Array,     # (GEN_NPARAMS,) f32
    d_block: int = 2048,
    interpret: bool = False,
    stats_dtype: str = "float32",
) -> tuple[jax.Array, jax.Array]:
    """Second generating pass: the filtered mean ξ = Σᵢ w_xi[i]·∇ᵢ and the
    Byzantine row-sum Σᵢ w_byz[i]·∇ᵢ (the adversary's feedback signal),
    both regenerated from the same counters as the sweep so nothing (m, d)
    is ever stored.  ``stats_dtype`` reproduces the materializing path's
    storage rounding for ξ; the byz sum uses raw f32 rows exactly as the
    host hands ``adversary.update_state`` the un-rounded attack output."""
    m = keys.shape[0]
    d = x.shape[0]
    m_pad = (-m) % 8
    d_pad = (-d) % d_block
    if m_pad:
        w_xi = jnp.pad(w_xi, (0, m_pad))
        w_byz = jnp.pad(w_byz, (0, m_pad))
        keys = jnp.pad(keys, ((0, m_pad), (0, 0)))
        skewsign = jnp.pad(skewsign, (0, m_pad))
        slot = jnp.pad(slot, (0, m_pad), constant_values=-1)
    if d_pad:
        x = jnp.pad(x, (0, d_pad))
        h = jnp.pad(h, (0, d_pad))
        x_star = jnp.pad(x_star, (0, d_pad))
        het_dir = jnp.pad(het_dir, (0, d_pad))
    mp = keys.shape[0]
    dp = x.shape[0]

    kernel = functools.partial(_gen_xi_kernel, d_block=d_block, d=d,
                               stats_dtype=jnp.dtype(stats_dtype))
    with jax.named_scope("guard/pallas_gen_xi"):
        xi, byz = pl.pallas_call(
            kernel,
            grid=(dp // d_block,),
            in_specs=[
                pl.BlockSpec((mp,), lambda i: (0,)),             # w_xi
                pl.BlockSpec((mp,), lambda i: (0,)),             # w_byz
                pl.BlockSpec((d_block,), lambda i: (i,)),        # x
                pl.BlockSpec((d_block,), lambda i: (i,)),        # h
                pl.BlockSpec((d_block,), lambda i: (i,)),        # x_star
                pl.BlockSpec((d_block,), lambda i: (i,)),        # het_dir
                pl.BlockSpec((mp, 2), lambda i: (0, 0)),         # keys
                pl.BlockSpec((mp,), lambda i: (0,)),             # skewsign
                pl.BlockSpec((mp,), lambda i: (0,)),             # slot
                pl.BlockSpec((GEN_NPARAMS,), lambda i: (0,)),    # params
            ],
            out_specs=[
                pl.BlockSpec((d_block,), lambda i: (i,)),
                pl.BlockSpec((d_block,), lambda i: (i,)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((dp,), jnp.float32),
                jax.ShapeDtypeStruct((dp,), jnp.float32),
            ],
            interpret=interpret,
        )(w_xi, w_byz, x, h, x_star, het_dir, keys, skewsign, slot, params)
    return xi[:d], byz[:d]
