"""ByzantineSGD as a first-class data-parallel gradient aggregation feature.

Each data-parallel slice of the mesh is one of the paper's m "worker
machines".  ``train_step`` computes per-worker gradients (vmap-of-grad with
the worker axis sharded over ('pod','data')), maintains the Algorithm-1
martingales per worker, filters, and replaces the usual psum-mean with a
masked filtered mean.  The filter itself is the *same* ``filter_update``
used by the single-host reference in :mod:`repro.core.byzantine_sgd` — only
the Gram matrices are produced differently.

Two guard modes (DESIGN.md §3):

* ``exact`` — paper-faithful.  The B_i martingale is a full parameter-sized
  pytree per worker (leading worker axis sharded over data, so each device
  stores exactly one worker's model-shard — the same footprint as one extra
  optimizer moment).  Gram matrices are leaf-wise ``einsum('w...,v...->wv')``
  contractions; XLA realizes the required all-gather of gradient shards
  over the data axis (the same order of communication mini-batch SGD's
  all-reduce already pays).  With ``incremental_gram`` (default) the
  B-Gram is carried in state and rank-updated from the gradient
  all-gather (DESIGN.md §5), so B shards themselves never travel.

* ``sketch`` — beyond-paper scalable variant.  Per-worker gradients are
  CountSketched (feature hashing: s_j = Σ_{h(i)=j} σ(i)·g_i, computed
  leaf-wise with an iota hash — no projection matrix is ever materialized)
  into k ≪ d dims.  Cross-worker inner products use the sketches (unbiased,
  variance ‖g_i‖‖g_j‖/√k); diagonal norms stay exact (free, local).  The
  data-axis communication drops from O(|params|) to O(W·k) and the B-state
  from |params| to k floats per worker.  Thresholds get a configurable
  slack factor to absorb sketch noise.

V (the Assumption-2.2 deviation bound) is rarely known for neural nets;
``auto_v`` calibrates it online as an EMA of the **25th percentile** of
pairwise distances between fresh worker gradients (DESIGN.md §3): good–good
pairs are a (1 − α)² ≥ 25% fraction of all pairs whenever α < 1/2, so the
25th percentile is always witnessed by an honest pair across the paper's
*entire* α < 1/2 regime.  The plain median is not: attacker-involved pairs
outnumber honest ones once 1 − (1 − α)² > 1/2, i.e. α > 1 − 1/√2 ≈ 0.29 —
safe at α = 0.25, inflatable well before the breakdown point.  Good
workers concentrate, so the chosen quantile ≈ 2·(typical deviation) and
halving it estimates V.

Both guard modes also run on the *flat* (m, d) convex harness as guard
backends ``dp_exact`` / ``dp_sketch`` (:mod:`repro.core.guard_backends`,
DESIGN.md §9): a stacked gradient array is a one-leaf worker pytree and
the iterate/anchor stand in for params, so the same ``guard_step`` is
sweepable under the scenario campaigns with no adaptation layer.  Since
the §10 unification the **trainer drives the same flat view**: LM
training ravels its gradient pytrees through
:mod:`repro.core.tree_harness` and selects these modes as the
``dp_exact`` / ``dp_sketch`` backends of ``SolverConfig.guard_backend``
(the pytree ``guard_step`` path below remains the mesh-sharded
realization the leaf-wise contractions were written for).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.byzantine_sgd import (
    GuardConfig,
    counting_median_index,
    filter_update,
    pairwise_sq_dists_from_gram,
    resolve_stats_dtype,
)

PyTree = Any


class DPGuardConfig(NamedTuple):
    n_workers: int
    T: int                       # planned total steps (enters C)
    V: float = 0.0               # 0 + auto_v → calibrated online
    D: float = 10.0              # trust-region diameter for the A-statistic
    delta: float = 1e-3
    mode: str = "sketch"         # 'exact' | 'sketch'
    sketch_dim: int = 4096
    sketch_slack: float = 1.5    # threshold multiplier absorbing sketch noise
    threshold_mode: str = "anytime"
    mean_over_alive: bool = True
    auto_v: bool = True
    v_ema: float = 0.9
    grad_radius_mult: float = 4.0
    # §Perf lever: False (default) materializes f32 copies of per-worker
    # gradients for every statistic (simple, paper-faithful numerics);
    # True keeps gradients in their native dtype and accumulates in f32
    # inside the contractions (preferred_element_type) — no param-sized
    # f32 temporaries, halved all-gather bytes.  The config axis
    # ``SolverConfig.stats_dtype='bf16'`` sets this implicitly (the two
    # knobs named the same lever before the axis existed).
    low_precision_stats: bool = False
    # Storage dtype of the B martingale ('f32' | 'bf16' — the stats-
    # precision axis of DESIGN.md §5 Numerics).  bf16 halves the guard's
    # resident state and the bytes every B-side pass moves; the per-step
    # rounding it introduces is bounded by gram_resync_every below.
    stats_dtype: str = "f32"
    # Incremental B-Gram (exact mode; DESIGN.md §5): maintain ⟨B_i, B_j⟩
    # across steps via G_B += B gᵀ + g Bᵀ + g gᵀ instead of re-contracting
    # the full B pytree.  The cross term reuses the gradient all-gather the
    # ∇-Gram already pays, so the per-step collective volume of the exact
    # guard halves (B shards never move).  False re-derives G_B from B
    # every step — the drift oracle.
    incremental_gram: bool = True
    # Every N steps re-derive gram_B from B (one full contraction), zeroing
    # the accumulated rounding of the incremental path — essential under
    # low_precision_stats, where each cross term rounds the local B shard
    # to bf16.  0 disables resync.
    gram_resync_every: int = 64

    def guard_config(self, v_eff) -> GuardConfig:
        # jnp scalar V is fine: GuardConfig.thresholds only multiplies by it
        return GuardConfig(
            m=self.n_workers, T=self.T, V=v_eff, D=self.D, delta=self.delta,
            threshold_mode=self.threshold_mode, mean_over_alive=self.mean_over_alive,
            grad_radius_mult=self.grad_radius_mult,
        )


class DPGuardState(NamedTuple):
    A: jax.Array                 # (W,)
    B: PyTree                    # sketch: (W, k); exact: pytree, leaves
    #                              (W, *leaf) — stored in cfg.stats_dtype
    alive: jax.Array             # (W,) bool
    k: jax.Array                 # () int32
    v_est: jax.Array             # () f32 — calibrated V (EMA)
    gram_B: jax.Array            # (W, W) ⟨B_i, B_j⟩ — incremental (DESIGN.md §5)


# ---------------------------------------------------------------------------
# tree ↔ worker-axis algebra
# ---------------------------------------------------------------------------

def _leaf_f32(x):
    return x.astype(jnp.float32)


def worker_vdot(ga: PyTree, gb: PyTree, low_precision: bool = False) -> jax.Array:
    """⟨g_i, h_i⟩ per worker. Leaves of ga have leading W; gb may either
    share the leading W or be unbatched (broadcast). With ``low_precision``
    inputs stay in native dtype and only the contraction accumulates f32
    (no param-sized f32 temporaries)."""
    def one(a, b):
        if not low_precision:
            a, b = _leaf_f32(a), _leaf_f32(b)
        elif a.dtype != b.dtype:
            # dot_general needs one dtype; round the broadcast operand
            # (delta) down to the gradient dtype — the same rounding the
            # dense stats path applies to its delta view
            b = b.astype(a.dtype)
        if b.ndim == a.ndim - 1:
            b = b[None]
        W = a.shape[0]
        return jax.lax.dot_general(
            a.reshape(W, -1), jnp.broadcast_to(b, a.shape).reshape(W, -1),
            (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
    parts = jax.tree_util.tree_map(one, ga, gb)
    return functools.reduce(jnp.add, jax.tree_util.tree_leaves(parts))


def worker_sq_norms(g: PyTree, low_precision: bool = False) -> jax.Array:
    return worker_vdot(g, g, low_precision)


def worker_cross_gram(g: PyTree, low_precision: bool = False) -> jax.Array:
    """Full (W, W) Gram — exact mode. The self-pair case of
    :func:`worker_pair_gram`; XLA inserts the data-axis all-gather of
    gradient shards."""
    return worker_pair_gram(g, g, low_precision)


def worker_pair_gram(ga: PyTree, gb: PyTree, low_precision: bool = False) -> jax.Array:
    """(W, W) cross-Gram ⟨a_i, b_j⟩ between two worker-stacked pytrees —
    the ``B gᵀ`` term of the incremental Gram update.  Only ``gb`` (the
    fresh gradients) needs gathering across the worker axis; ``ga`` (the
    B martingale) is consumed at its home shard, so the exact guard's
    B-sized all-gather disappears.  With ``low_precision`` the gradient
    operand stays in its native dtype — reusing the same half-width
    gather ``gram_g`` already pays — and the *local* B shard is rounded
    down to match (dot_general needs one dtype; rounding the ungathered
    side keeps the wire bytes halved), accumulating in f32 as usual."""
    def one(a, b):
        if low_precision:
            a = a.astype(b.dtype)
        else:
            a, b = _leaf_f32(a), _leaf_f32(b)
        a2 = a.reshape(a.shape[0], -1)
        b2 = b.reshape(b.shape[0], -1)
        return jax.lax.dot_general(
            a2, b2, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
    parts = jax.tree_util.tree_map(one, ga, gb)
    return functools.reduce(jnp.add, jax.tree_util.tree_leaves(parts))


# ---------------------------------------------------------------------------
# CountSketch (sketch mode)
# ---------------------------------------------------------------------------

def _sign_iota(n: int, salt: int) -> jax.Array:
    """Deterministic ±1 per coordinate via a Knuth multiplicative hash of the
    flat index — generated on the fly, nothing stored."""
    idx = jax.lax.iota(jnp.uint32, n)
    h = (idx + jnp.uint32((salt * 0x9E3779B9 + 1) & 0xFFFFFFFF)) * jnp.uint32(2654435761)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    return 1.0 - 2.0 * (h & 1).astype(jnp.float32)


def sketch_tree(g: PyTree, k: int, low_precision: bool = False) -> jax.Array:
    """CountSketch each worker's gradient into (W, k).

    Bucketing is the *stride* pattern (coordinate i → bucket i mod k), which
    with independent random signs is still an unbiased CountSketch
    (E⟨s_i, s_j⟩ = ⟨g_i, g_j⟩ holds for any fixed bucketing; only the signs
    must be random).  The stride form is a pad+reshape+reduce — no scatter —
    which both maps onto TPU reductions and avoids XLA SPMD's scatter
    partitioner on multi-axis-sharded operands.

    ``low_precision``: sign-flip in the gradient's native dtype (±1 is
    exact in bf16) and accumulate the fold in f32 — avoids an f32 copy of
    the whole gradient."""
    leaves = jax.tree_util.tree_leaves(g)
    out = jnp.zeros((leaves[0].shape[0], k), jnp.float32)
    for salt, leaf in enumerate(leaves):
        W = leaf.shape[0]
        flat = (leaf if low_precision else _leaf_f32(leaf)).reshape(W, -1)
        n = flat.shape[1]
        sign = _sign_iota(n, salt).astype(flat.dtype)
        signed = flat * sign[None, :]
        pad = (-n) % k
        if pad:
            signed = jnp.pad(signed, ((0, 0), (0, pad)))
        out = out + jnp.sum(signed.reshape(W, -1, k), axis=1, dtype=jnp.float32)
    return out


def sketch_gram(s: jax.Array, sq_norms: jax.Array) -> jax.Array:
    """Gram from sketches with the exact diagonal patched in (norms are
    local/free; only cross terms need the sketch estimate)."""
    G = s @ s.T
    W = s.shape[0]
    return G.at[jnp.arange(W), jnp.arange(W)].set(sq_norms)


# ---------------------------------------------------------------------------
# the guard
# ---------------------------------------------------------------------------

def init_guard_state(cfg: DPGuardConfig, params_like: PyTree) -> DPGuardState:
    W = cfg.n_workers
    sdt = resolve_stats_dtype(cfg.stats_dtype)
    if cfg.mode == "sketch":
        B = jnp.zeros((W, cfg.sketch_dim), sdt)
    else:
        B = jax.tree_util.tree_map(
            lambda x: jnp.zeros((W, *x.shape), sdt), params_like
        )
    return DPGuardState(
        A=jnp.zeros((W,), jnp.float32),
        B=B,
        alive=jnp.ones((W,), bool),
        k=jnp.zeros((), jnp.int32),
        v_est=jnp.zeros((), jnp.float32),
        gram_B=jnp.zeros((W, W), jnp.float32),
    )


def _masked_quantile(x: jax.Array, mask: jax.Array, q: float) -> jax.Array:
    """``jnp.quantile(x[mask], q)`` with a traced mask and static shapes —
    the same linear-interpolation formula, so an all-True mask is
    bit-identical to the unmasked quantile."""
    n = jnp.sum(mask)
    s = jnp.sort(jnp.where(mask, x, jnp.inf))
    index = q * jnp.maximum(n - 1, 0).astype(jnp.float32)
    low = jnp.floor(index)
    high = jnp.ceil(index)
    low_val = s[low.astype(jnp.int32)]
    high_val = s[high.astype(jnp.int32)]
    w = index - low
    return low_val * (1.0 - w) + high_val * w


def v_from_gram(gram_g: jax.Array, report: jax.Array | None = None) -> jax.Array:
    """The Assumption-2.2 scale convention: half the 25th-percentile
    pairwise distance from a fresh-gradient Gram.

    Invariant behind the 0.25 quantile (NOT the median): for α < 1/2,
    good-good pairs are a (1-α)² > (1/2)² = 25% fraction of all pairs, so
    the 25th percentile is always witnessed by an honest pair — a
    Byzantine-proof estimate of the honest deviation scale over the whole
    α < 1/2 regime.  The median only survives attacker-pair fractions
    below 1/2, which fails once α > 1−1/√2 ≈ 0.29 (e.g. at α=0.375 with
    m=8, 18 of 28 pairs involve an attacker and the median is theirs).

    Single source of the convention: the guards' auto-V calibration below
    and the trainer's adversary ``ctx["V"]`` estimate (DESIGN.md §10) both
    call this, so the attack magnitudes always probe the same radius the
    filter enforces.

    ``report`` (optional (m,) bool reporting mask, DESIGN.md §13) restricts
    the quantile to pairs where *both* endpoints reported — a zero-masked
    non-reporter row would otherwise inject spurious ‖g_j‖-sized distances
    into the estimate.
    """
    d2 = pairwise_sq_dists_from_gram(gram_g)
    W = d2.shape[0]
    iu, ju = jnp.triu_indices(W, k=1)
    off = d2[iu, ju]
    if report is None:
        q = jnp.quantile(off, 0.25)
    else:
        q = _masked_quantile(off, report[iu] & report[ju], 0.25)
    return jnp.sqrt(q) * 0.5


def _calibrate_v(
    cfg: DPGuardConfig, gram_g: jax.Array, v_prev: jax.Array,
    report: jax.Array | None = None,
) -> jax.Array:
    if not cfg.auto_v:
        return jnp.asarray(cfg.V, jnp.float32)
    v_now = v_from_gram(gram_g, report)
    v_new = jnp.where(v_prev > 0, cfg.v_ema * v_prev + (1 - cfg.v_ema) * v_now, v_now)
    return jnp.maximum(v_new, 1e-12)


def guard_step(
    cfg: DPGuardConfig,
    state: DPGuardState,
    grads_w: PyTree,          # leaves (W, ...) — worker axis sharded over data
    params: PyTree,
    anchor: PyTree,           # x_1 — the A-statistic reference point
    report: jax.Array | None = None,  # (W,) bool — who reported this step
) -> tuple[DPGuardState, PyTree, dict]:
    """One filter + aggregation step. Returns (state', ξ (params-like), diag)."""
    W = cfg.n_workers
    k_new = state.k + 1
    lp = cfg.low_precision_stats
    sdt = resolve_stats_dtype(cfg.stats_dtype)
    # guard/stats_sweep (DESIGN.md §12): the entry rounding, the report
    # masking and the martingale block, under the dense/fused guards' name
    with jax.named_scope("guard/stats_sweep"):
        if sdt != jnp.dtype(jnp.float32):
            # the single entry rounding of the stats axis (same convention as
            # the dense/fused guards): every statistic below — A, both Grams,
            # the cross term, B, ξ — consumes the *rounded* gradients, so the
            # incremental Gram tracks the same martingale the bf16 B storage
            # actually accumulates (a no-op when the trainer already ravelled
            # to bf16; f32 flat-harness inputs are rounded here)
            grads_w = jax.tree_util.tree_map(lambda g: g.astype(sdt), grads_w)

        def _mask_workers(g: PyTree) -> PyTree:
            # entry masking for partial participation (DESIGN.md §13): zero
            # rows freeze A/B for non-reporters and keep the incremental-Gram
            # identity exact, so every contraction below runs unchanged
            return jax.tree_util.tree_map(
                lambda x: jnp.where(
                    report.reshape((-1,) + (1,) * (x.ndim - 1)), x,
                    jnp.zeros((), x.dtype)),
                g,
            )

        if report is not None:
            grads_w = _mask_workers(grads_w)

        # --- martingale updates -------------------------------------------------
        if lp:
            delta = jax.tree_util.tree_map(
                lambda p, a: (p.astype(jnp.float32) - a.astype(jnp.float32)).astype(p.dtype),
                params, anchor,
            )
        else:
            delta = jax.tree_util.tree_map(
                lambda p, a: _leaf_f32(p) - _leaf_f32(a), params, anchor
            )
        A = state.A + worker_vdot(grads_w, delta, lp)

        sq_g = worker_sq_norms(grads_w, lp)
        if cfg.mode == "sketch":
            # Center before sketching: pairwise distances are invariant under a
            # common shift, but sketch noise scales with the norms of what is
            # sketched — ‖g_i − ḡ‖ (the deviation scale, what the filter
            # measures) instead of ‖g_i‖ (huge and common-mode). One extra
            # mean-reduce of the gradients, orders less than exact mode's
            # all-gather.
            if report is None:
                n_mean = None
            else:
                # reporter-count mean: masked rows are already zero, so the sum
                # runs over reporters — only the divisor changes
                n_mean = jnp.maximum(jnp.sum(report), 1).astype(jnp.float32)
            if lp:
                g_mean = jax.tree_util.tree_map(
                    lambda g: (jnp.mean(g, axis=0, keepdims=True, dtype=jnp.float32)
                               if n_mean is None else
                               jnp.sum(g, axis=0, keepdims=True, dtype=jnp.float32)
                               / n_mean).astype(g.dtype), grads_w
                )
                g_cent = jax.tree_util.tree_map(lambda g, c: g - c, grads_w, g_mean)
            else:
                g_mean = jax.tree_util.tree_map(
                    lambda g: (jnp.mean(_leaf_f32(g), axis=0, keepdims=True)
                               if n_mean is None else
                               jnp.sum(_leaf_f32(g), axis=0, keepdims=True) / n_mean),
                    grads_w,
                )
                g_cent = jax.tree_util.tree_map(
                    lambda g, c: _leaf_f32(g) - c, grads_w, g_mean
                )
            if report is not None:
                # re-mask after centering: a non-reporter's centered row would
                # be −ḡ (not 0) and leak into its frozen B sketch
                g_cent = _mask_workers(g_cent)
            sq_cent = worker_sq_norms(g_cent, lp)
            s_g = sketch_tree(g_cent, cfg.sketch_dim, lp)
            # (W, k) sketch state: stored in the stats dtype, accumulated and
            # contracted in f32 (the sketch is tiny — the cast is free)
            B = (state.B.astype(jnp.float32) + s_g).astype(sdt)
            gram_g = sketch_gram(s_g, sq_cent)
            B32 = B.astype(jnp.float32)
            gram_B = sketch_gram(B32, jnp.sum(B32 * B32, axis=-1))
        else:
            if lp:
                # no param-sized f32 temporaries: native-dtype add, stored in
                # the stats dtype (the one new rounding of the bf16 axis)
                B = jax.tree_util.tree_map(
                    lambda b, g: (b + g.astype(b.dtype)).astype(sdt),
                    state.B, grads_w,
                )
            else:
                B = jax.tree_util.tree_map(
                    lambda b, g: (_leaf_f32(b) + _leaf_f32(g)).astype(sdt),
                    state.B, grads_w,
                )
            gram_g = worker_cross_gram(grads_w, lp)
            if cfg.incremental_gram:
                def _incremental():
                    # G_B^k = G_B^{k-1} + B gᵀ + g Bᵀ + g gᵀ — no contraction
                    # over (and no all-gather of) the accumulated B pytree
                    cross = worker_pair_gram(state.B, grads_w, lp)
                    return state.gram_B + cross + cross.T + gram_g

                if cfg.gram_resync_every > 0:
                    # zero the accumulated rounding (bf16 cross terms under lp)
                    # with a from-scratch contraction every N-th step; both
                    # alternatives live inside the cond so only one is paid
                    gram_B = jax.lax.cond(
                        k_new % cfg.gram_resync_every == 0,
                        lambda: worker_cross_gram(B, lp),
                        _incremental,
                    )
                else:
                    gram_B = _incremental()
            else:
                gram_B = worker_cross_gram(B, lp)

    # --- V calibration + filter --------------------------------------------
    # guard/filter named scope (DESIGN.md §12 span convention): the dp
    # backends share the dense/fused phase names so one XLA profile query
    # attributes filter time across all four realizations
    with jax.named_scope("guard/filter"):
        v_eff = _calibrate_v(cfg, gram_g, state.v_est, report)
        slack = cfg.sketch_slack if cfg.mode == "sketch" else 1.0
        gcfg = cfg.guard_config(v_eff * slack)
        good_k, diag = filter_update(
            A, gram_B, gram_g, state.alive, k_new, gcfg, report
        )

    # --- filtered mean (the paper's ξ_k) -------------------------------------
    # ξ averages the gradients that actually arrived: good ∩ reporting
    contrib = good_k if report is None else good_k & report
    denom = jnp.where(
        cfg.mean_over_alive, jnp.maximum(jnp.sum(contrib), 1), W
    ).astype(jnp.float32)
    w = contrib.astype(jnp.float32) / denom
    with jax.named_scope("guard/aggregate"):
        if lp:
            # fused mask-and-reduce in native dtype, f32 accumulation — this
            # is what the filtered_mean Pallas kernel computes on TPU
            xi = jax.tree_util.tree_map(
                lambda g: jnp.einsum(
                    "w,w...->...", w.astype(g.dtype), g,
                    preferred_element_type=jnp.float32,
                ).astype(g.dtype),
                grads_w,
            )
        else:
            xi = jax.tree_util.tree_map(
                lambda g: jnp.einsum("w,w...->...", w, _leaf_f32(g)).astype(g.dtype),
                grads_w,
            )

    diag = dict(diag, v_est=v_eff, sq_norm_mean=jnp.mean(sq_g))
    new_state = DPGuardState(A=A, B=B, alive=good_k, k=k_new, v_est=v_eff,
                             gram_B=gram_B)
    return new_state, xi, diag


# ---------------------------------------------------------------------------
# gradient-level attack simulation on the worker axis
# ---------------------------------------------------------------------------

def apply_tree_attack(
    name: str, key: jax.Array, grads_w: PyTree, byz_mask: jax.Array, scale: float = 3.0,
) -> PyTree:
    """Overwrite Byzantine workers' gradient trees. ``byz_mask``: (W,) bool."""
    def mask_like(leaf):
        return byz_mask.reshape((-1,) + (1,) * (leaf.ndim - 1))

    if name == "none":
        return grads_w
    if name == "sign_flip":
        return jax.tree_util.tree_map(
            lambda g: jnp.where(mask_like(g), -scale * g, g), grads_w
        )
    if name == "noise":
        leaves, treedef = jax.tree_util.tree_flatten(grads_w)
        keys = jax.random.split(key, len(leaves))
        noisy = [
            jnp.where(mask_like(g), scale * jax.random.normal(kk, g.shape, g.dtype), g)
            for kk, g in zip(keys, leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, noisy)
    if name == "constant_drift":
        return jax.tree_util.tree_map(
            lambda g: jnp.where(mask_like(g), jnp.full_like(g, scale / jnp.sqrt(jnp.float32(g[0].size))), g),
            grads_w,
        )
    if name == "scaled_copy":
        # colluders send mean-of-good × scale — inflates the step magnitude
        def one(g):
            mu = jnp.mean(jnp.where(mask_like(g), 0, g), axis=0, keepdims=True)
            n_good = jnp.maximum(jnp.sum(~byz_mask), 1)
            mu = mu * (byz_mask.shape[0] / n_good)
            return jnp.where(mask_like(g), scale * mu, g)
        return jax.tree_util.tree_map(one, grads_w)
    raise KeyError(f"unknown tree attack {name!r}")
