"""Counter-based gradient generation — the producer side of the
on-device campaign story (DESIGN.md §14).

Campaign scale used to be bounded by HBM: every run of the one-jit grid
materialized its (m, d) stochastic-gradient batch per step.  This module
holds the *shared* generation math — a pure-``jnp`` Threefry-2x32
implementation plus the mean/noise/heterogeneity terms — so the exact
same expressions run in two places:

* on the host, as ``Problem.stoch_grad`` / ``Problem.het_grad`` of a
  :func:`repro.data.problems.make_generated_problem` problem, and
* inside the fused guard sweep (``kernels/fused_guard.py``), which
  regenerates each worker's strip from ``(key, coordinate)`` counters and
  streams it straight through the Gram/A/B update without ever writing
  the (m, d) batch to HBM.

Because both sides call the *same functions* in the same order, in-kernel
strips are bit-exact against the host generator by construction — the
differential oracle in ``tests/test_gradgen.py`` pins this, not a
tolerance band.

Key-chain contract
------------------
``run_sgd`` derives ``worker_keys = jax.random.split(gkey, m)`` exactly as
the materializing path does; the generated problem consumes only the
raw ``uint32[2]`` key data of each worker key.  The noise bits for
coordinate ``j`` are ``threefry2x32(k0, k1, 0, j)[0]`` — keyed on
(worker, coordinate), with the (run, step) dependence carried entirely by
the key chain (``gkey`` differs per run row and per step).  Bits map to a
centered uniform via the standard 23-bit mantissa ladder, and the noise
scale ``V/sqrt(d)`` keeps ``‖noise‖ ≤ V`` almost surely (Assumption 2.2).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# Threefry-2x32 rotation schedule (Salmon et al. 2011), 20 rounds in five
# groups of four; even groups rotate by R_A, odd groups by R_B.
_R_A = (13, 15, 26, 6)
_R_B = (17, 29, 16, 24)
_PARITY = 0x1BD11BDA


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << r) | (x >> (32 - r))


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32, 20 rounds — pure ``jnp`` uint32 ops, so the same
    function body runs on host arrays and inside Pallas kernel strips.
    All four operands broadcast; returns the two output words."""
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    ks2 = k0 ^ k1 ^ jnp.uint32(_PARITY)
    x0 = jnp.asarray(c0, jnp.uint32) + k0
    x1 = jnp.asarray(c1, jnp.uint32) + k1
    # key-injection schedule after each 4-round group
    inject = ((k1, ks2, 1), (ks2, k0, 2), (k0, k1, 3),
              (k1, ks2, 4), (ks2, k0, 5))
    for g, (ka, kb, inc) in enumerate(inject):
        rots = _R_A if g % 2 == 0 else _R_B
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ka
        x1 = x1 + kb + jnp.uint32(inc)
    return x0, x1


def centered_uniform(bits: jax.Array) -> jax.Array:
    """uint32 bits → f32 uniform in (−1, 1): the top 23 bits land on the
    open-interval lattice ((b >> 9) + 0.5)·2⁻²³ ∈ (0, 1), then center."""
    # b >> 9 < 2^23 is exact through int32; Mosaic has no uint32 → f32 cast
    top = (bits >> 9).astype(jnp.int32).astype(jnp.float32)
    u = (top + 0.5) * jnp.float32(2.0 ** -23)
    return 2.0 * u - 1.0


def key_bits(key: jax.Array) -> jax.Array:
    """Raw ``uint32[..., 2]`` words of a PRNG key — accepts both legacy
    uint32 keys and new-style typed keys."""
    if jnp.issubdtype(key.dtype, jnp.integer):
        return key.astype(jnp.uint32)
    return jax.random.key_data(key).astype(jnp.uint32)


def noise_bits(k0, k1, j: jax.Array) -> jax.Array:
    """Noise bits for coordinate counter ``j`` under worker key words
    (k0, k1): word 0 of ``threefry2x32(k0, k1, 0, j)``.  ``j`` is the
    *global* coordinate index — kernel strips pass the block-offset iota
    so every block reproduces the host's full-length stream."""
    return threefry2x32(k0, k1, jnp.zeros_like(j), j)[0]


def mean_grad(h: jax.Array, x: jax.Array, x_star: jax.Array) -> jax.Array:
    """∇f(x) of the diagonal quadratic f(x) = ½ Σ hⱼ (xⱼ − x*ⱼ)² —
    coordinate-local, so a kernel strip computes its slice exactly."""
    return h * (x - x_star)


def noise_row(kd: jax.Array, j: jax.Array, noise_scale) -> jax.Array:
    """One worker's noise slice at global coordinates ``j`` (uint32):
    ``noise_scale · centered_uniform(bits)``.  ``kd`` is the worker's
    ``uint32[2]`` key data."""
    return noise_scale * centered_uniform(noise_bits(kd[0], kd[1], j))


class GenSpec(NamedTuple):
    """Everything a kernel needs to regenerate one worker-strip.

    Coordinate-wise problem data (``h``, ``x_star``) streams through the
    same BlockSpecs as the gradient strips; ``het_dir`` is the rank-1
    heterogeneity direction (zeros for a homogeneous fleet) whose
    per-worker sign/scale rides in as the O(m) ``skewsign`` vector.
    ``het_sign`` is the per-worker ±1 of that rank-1 factorization
    (``None`` until :func:`repro.data.problems.heterogenize_generated`
    sets it) — the solver multiplies it into the profile's skew to form
    ``skewsign``; a problem heterogenized through the *dense* wrapper has
    no such factorization and is rejected by the gen gate.
    """

    h: jax.Array            # (d,) diagonal curvature
    x_star: jax.Array       # (d,) optimum
    noise_scale: jax.Array  # () f32 — V/sqrt(d), ‖noise‖ ≤ V a.s.
    het_dir: jax.Array      # (d,) rank-1 skew direction; zeros if iid
    het_sign: jax.Array | None = None  # (m,) ±1 f32; None until heterogenized


# ---------------------------------------------------------------------------
# in-kernel attack parameterization
# ---------------------------------------------------------------------------
#
# The scenario engine's per-row attack dispatch (repro.scenarios.adversary,
# a lax.switch over (m, d) arrays) collapses, for the generated-problem
# family, to an O(1)-per-worker parameter vector: every supported attack's
# Byzantine row is an affine function of quantities a strip can compute
# locally (the honest mean/std of the strip, the true-gradient strip, a
# per-worker constant).  ``GEN_PARAMS`` entries — slots a/b are the
# scenario's two coalition phases:
#
#   id    — effective ATTACK_TABLE id (retreat_on_filter is remapped to
#           inner_product/none on its scalar coalition-intact condition
#           before the kernel sees it)
#   sf    — sign_flip row factor:        row = sf · g          (sf = −3·scale)
#   z     — alie/alie_update deviation:  row = μ ∓ z·σ         (z = z_scale·z_max)
#   const — constant_drift / hidden_shift per-coordinate constant
#           (knob·V/√d; drift row = const, hidden row = t + const)
#   ipc   — inner_product pull:          row = t − ipc·t/‖t‖   (ipc = (1+s)·V)
#
# plus the two shared scalars ``tg_nrm`` (max(‖∇f(x)‖, 1e-12), the
# inner-product normalizer — O(d) on the host, not per-strip) and the
# problem's ``noise_scale``.  Unsupported in-kernel: random_gaussian (id 2,
# consumes a PRNG key per step) and mirror (needs a second problem).
GEN_NPARAMS = 12
(P_ID_A, P_SF_A, P_Z_A, P_CONST_A, P_IPC_A,
 P_ID_B, P_SF_B, P_Z_B, P_CONST_B, P_IPC_B,
 P_TGNRM, P_NSCALE) = range(GEN_NPARAMS)

# ATTACK_TABLE ids the generated path supports (repro.scenarios.adversary
# pins the table order; tests assert the two stay in sync)
GEN_SUPPORTED_IDS = (0, 1, 3, 4, 5, 6, 7, 8)


class GenStepCtx(NamedTuple):
    """Per-step adversary/worker inputs of the generating guard sweep —
    everything O(m) or O(1); the (m, d) batch it stands in for is never
    materialized.  Built by ``ScenarioAdversary.gen_attack_ctx`` + the
    solver's key chain each scan step."""

    worker_keys: jax.Array  # (m, 2) uint32 — key_bits of split(gkey, m)
    skewsign: jax.Array     # (m,) f32 — profile.skew · het_sign (0 = iid)
    slot: jax.Array         # (m,) int32 — 0 honest, 1 phase-a, 2 phase-b
    params: jax.Array       # (GEN_NPARAMS,) f32 — see above
    w_byz: jax.Array        # (m,) f32 — mask_k, for the feedback byz-row sum


def gen_worker_rows(x, h, x_star, het_dir, keys, skewsign, slot, params, j, d):
    """Regenerate + attack all worker rows for one coordinate strip.

    Pure ``jnp`` — the *same* function body is the Pallas kernel core
    (called per (m, d_blk) strip) and the host oracle (called once with
    ``j = arange(d)``), which is what makes kernel-vs-host parity exact by
    construction rather than by tolerance.

    Args:
      x, h, x_star, het_dir: (blk,) coordinate strips (f32).
      keys: (mp, 2) uint32 worker key words (padded rows arbitrary).
      skewsign: (mp,) f32 per-worker skew·sign (0 disables the het term).
      slot: (mp,) int32 — 0 honest, 1 attack-a, 2 attack-b, −1 padding.
      params: (GEN_NPARAMS,) f32 — see module comment.
      j: (blk,) or (1, blk) uint32 *global* coordinate indices.
      d: static true dimension — coords ≥ d are zero-masked (generated
         noise is nonzero in padded lanes, unlike zero-padded inputs).

    Returns (mp, blk) f32 attacked rows; invalid rows/coords are zeroed,
    mirroring the materializing path's zero padding.
    """
    p = params
    jm = j.reshape(1, -1)
    # per-worker vectors become (mp, 1) columns before any comparison:
    # Mosaic cannot reshape a boolean vector into a column
    slot = slot[:, None]
    skewsign = skewsign[:, None]
    t = mean_grad(h, x, x_star)                              # true-grad strip
    bits = threefry2x32(keys[:, 0][:, None], keys[:, 1][:, None],
                        jnp.zeros_like(jm), jm)[0]           # (mp, blk)
    g = t[None, :] + p[P_NSCALE] * centered_uniform(bits)
    g = jnp.where(skewsign != 0.0, g + skewsign * het_dir[None, :], g)

    # honest strip moments — the expressions of attacks._good_row_stats
    # (population moments over honest rows; coordinate-local, so the strip
    # slice equals the full-width computation)
    w = (slot == 0).astype(jnp.float32)
    n_good = jnp.maximum(jnp.sum(w), 1.0)
    mu = jnp.sum(g * w, axis=0) / n_good
    var = jnp.sum(w * (g - mu[None, :]) ** 2, axis=0) / n_good
    sig = jnp.sqrt(var + 1e-12)
    gn = t / p[P_TGNRM]

    use_b = slot == 2
    aid = jnp.where(use_b, p[P_ID_B], p[P_ID_A])
    sf = jnp.where(use_b, p[P_SF_B], p[P_SF_A])
    zf = jnp.where(use_b, p[P_Z_B], p[P_Z_A])
    cst = jnp.where(use_b, p[P_CONST_B], p[P_CONST_A])
    ipc = jnp.where(use_b, p[P_IPC_B], p[P_IPC_A])

    # where-chain instead of lax.switch: ids are per-*worker* here, and
    # every branch is a cheap affine row — ids 0/2 (none / the unsupported
    # random_gaussian) fall through to the honest row
    row = g
    row = jnp.where(aid == 1.0, sf * g, row)
    row = jnp.where(aid == 3.0, cst + jnp.zeros_like(g), row)
    row = jnp.where(aid == 4.0, mu[None, :] - zf * sig[None, :], row)
    row = jnp.where(aid == 8.0, mu[None, :] + zf * sig[None, :], row)
    row = jnp.where(aid == 5.0, t[None, :] - ipc * gn[None, :], row)
    row = jnp.where(aid == 6.0, t[None, :] + cst, row)
    out = jnp.where(slot > 0, row, g)

    keep = (slot >= 0) & (jm < jnp.uint32(d))
    return jnp.where(keep, out, 0.0)
