"""Distributed training step: per-worker grads → guard backend → optimizer
(DESIGN.md §10).

``build_train_step`` returns a pure function suitable for ``jax.jit`` with
mesh shardings:

    state', metrics = train_step(state, batch, byz_rank, key)

* ``batch`` leaves are (W, per_worker_batch, ...) with W sharded over the
  mesh's worker axes ('pod','data').
* per-worker gradients come from vmap-of-grad: XLA partitions the vmap over
  the data axis, so each data slice computes exactly its own worker's
  gradient (params replicated over data, tensor-sharded over model).
* the gradient pytree is presented to the aggregation layer through the
  **tree harness** (:mod:`repro.core.tree_harness`): ravelled to the flat
  ``(W, d)`` stacked view every guard backend, attack, and scenario
  adversary of the convex harness already consumes, with ξ unravelled back
  into a parameter-shaped update.  There is no trainer-specific guard
  implementation — ``SolverConfig.guard_backend`` selects ``dense`` /
  ``fused`` / ``dp_exact`` / ``dp_sketch`` exactly as ``run_sgd`` does, and
  stateless baselines (mean / coordinate median / trimmed mean / Krum /
  geometric median) come from the same :func:`repro.core.solver.make_aggregator`
  with Krum's f sized by the shared ⌈αm⌉ convention
  (:func:`repro.core.solver.ceil_byzantine_count`).
* ``byz_rank`` is the (W,) int32 per-worker rank (worker w is Byzantine iff
  its rank is below the realized count — :func:`repro.core.solver.byz_rank`);
  scenario adversaries re-derive a *per-step* mask from it (churn, late
  join), static attacks evaluate it once.
* the adversary is either the static ``cfg.attack`` from the flat zoo or a
  :class:`repro.scenarios.adversary.ScenarioAdversary` (duck-typed — any
  object with ``mask_at`` / ``init_state`` / ``attack`` / ``update_state``),
  whose ``AdvState`` is carried in :class:`TrainState` next to the guard
  state, with the Remark-2.3 feedback (previous ξ, alive, n_alive) fed to
  every attack's ``ctx``.

Training-specific ``ctx`` semantics (the solver knows the true gradient;
the trainer cannot): ``ctx["true_grad"]`` is the omniscient adversary's
best estimate — the mean of the *honest* rows of the current flat gradient
matrix — and ``ctx["V"]`` is the explicit ``V`` when given, else an
instantaneous estimate from the pre-attack (all-honest) gradient spread
(half the 25th-percentile pairwise distance, the dp guards' auto-V
convention) — computed for *every* aggregator, so V-scaled attacks hit
stateless baselines too, not only the calibrating guards.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import attacks as attack_lib
from repro.core.byzantine_sgd import resolve_stats_dtype
from repro.core.solver import SolverConfig, make_aggregator
from repro.core.tree_harness import FlatSpec, params_harness
from repro.distributed.byzantine_dp import v_from_gram
from repro.models.model import LanguageModel
from repro.obs.telemetry import telemetry_on
from repro.optim.optimizers import Optimizer
from repro.utils import tree_add

PyTree = Any


class TrainState(NamedTuple):
    """Everything one training run carries across steps — and everything a
    checkpoint must round-trip for resume-equals-uninterrupted (params AND
    optimizer moments AND guard martingales AND the anchor AND the
    adversary/feedback memory)."""

    params: PyTree
    opt_state: PyTree
    guard: PyTree             # backend-specific aggregator state (scan-carried)
    anchor: jax.Array         # (d,) flat x₁ — the A-statistic reference point
    step: jax.Array           # () int32
    ever_byz: jax.Array       # (W,) bool — workers that were *ever* Byzantine
    adv: PyTree               # adversary memory (scalar zero when static)
    prev_xi: jax.Array        # (d,) ξ_{k-1} — Remark-2.3 feedback
    prev_alive: jax.Array     # (W,) bool — good_{k-1}
    prev_n_alive: jax.Array   # () int32
    grad_buf: PyTree = ()     # (W, d) stale-gradient buffer when the run
    #                           carries a WorkerProfile delay schedule with
    #                           cfg.max_delay > 0 (DESIGN.md §13); the empty
    #                           tuple otherwise (the `adv` scalar-zero
    #                           convention: no leaves, no trace change)


def rank_from_mask(mask: jax.Array) -> jax.Array:
    """(W,) int32 rank with the mask's Byzantine workers ranked first, so
    ``rank < sum(mask)`` reproduces ``mask`` — the bridge from the
    historical bool-mask API to the rank convention."""
    return jnp.argsort(jnp.argsort(~mask)).astype(jnp.int32)


def _estimate_v(flat: jax.Array) -> jax.Array:
    """Instantaneous Assumption-2.2 scale from the *pre-attack* (all-honest)
    gradient rows — the guards' own :func:`v_from_gram` convention, so it is
    computable for every aggregator (the omniscient Remark-2.3 adversary can
    always measure the honest spread itself) and can never diverge from the
    radius the auto-V guards enforce.  Gram in f32 regardless of the flat
    view's storage dtype — the V scale must not wobble with stats_dtype."""
    f32 = flat.astype(jnp.float32)
    return jnp.maximum(v_from_gram(f32 @ f32.T), 1e-12)


def _grad_dtype(cfg: SolverConfig, harness) -> jnp.dtype:
    """Storage dtype of the (W, d) flat gradient view — the cast-once-at-
    ravel rule (DESIGN.md §5 Numerics): the guard's statistics dtype when
    the precision axis is lowered, else the harness dtype."""
    stats_jdt = resolve_stats_dtype(cfg.stats_dtype)
    return (stats_jdt if stats_jdt != jnp.dtype(jnp.float32)
            else harness.flat_dtype)


def _validate(cfg: SolverConfig, V: float) -> None:
    if (cfg.aggregator == "byzantine_sgd"
            and cfg.guard_backend in ("dense", "fused") and V <= 0):
        raise ValueError(
            f"guard backend {cfg.guard_backend!r} has no online auto-V; "
            "pass an explicit V (Assumption-2.2 deviation bound) or select "
            "an auto-V-capable backend (dp_exact / dp_sketch)"
        )


def init_train_state(
    model: LanguageModel,
    optimizer: Optimizer,
    cfg: SolverConfig,
    key: jax.Array,
    *,
    V: float = 0.0,
    D: float = 10.0,
    adversary=None,
) -> TrainState:
    _validate(cfg, V)
    harness = params_harness(model)
    params = model.init(key)
    guard0, _ = make_aggregator(FlatSpec(harness.d, V, D), cfg)
    adv0 = (adversary.init_state(cfg.m, harness.d) if adversary is not None
            else jnp.zeros(()))
    # stale-gradient buffer (DESIGN.md §13): carried only when the run's
    # adversary holds a WorkerProfile delay schedule and cfg.max_delay arms
    # it — every schedule refreshes at step 0, so the zeros are never
    # consumed.  Same dtype as the flat gradient view (_grad_dtype).
    stale_on = (getattr(adversary, "profile", None) is not None
                and cfg.max_delay > 0)
    grad_buf0 = (jnp.zeros((cfg.m, harness.d), _grad_dtype(cfg, harness))
                 if stale_on else ())
    return TrainState(
        params=params,
        opt_state=optimizer.init(params),
        guard=guard0,
        anchor=harness.ravel(params),
        step=jnp.zeros((), jnp.int32),
        ever_byz=jnp.zeros((cfg.m,), bool),
        adv=adv0,
        prev_xi=jnp.zeros((harness.d,), harness.flat_dtype),
        prev_alive=jnp.ones((cfg.m,), bool),
        prev_n_alive=jnp.asarray(cfg.m, jnp.int32),
        grad_buf=grad_buf0,
    )


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def build_train_step(
    model: LanguageModel,
    optimizer: Optimizer,
    cfg: SolverConfig,
    *,
    V: float = 0.0,
    D: float = 10.0,
    adversary=None,
    telemetry=None,
) -> Callable:
    """Returns train_step(state, batch, byz_rank, key) → (state', metrics).

    ``cfg`` is the *same* :class:`~repro.core.solver.SolverConfig` the flat
    harness uses: ``aggregator`` / ``guard_backend`` / ``guard_opts`` select
    the aggregation path, ``attack`` / ``attack_kwargs`` the static
    adversary (ignored when ``adversary`` is given), ``alpha`` the realized
    Byzantine fraction (floor — whole workers), and ``m`` / ``T`` /
    ``threshold_mode`` / ``mean_over_alive`` / ``delta`` the filter.
    ``cfg.eta`` is unused — the optimizer owns the learning rate.

    ``key`` is the per-step attack/adversary key (callers derive it from a
    dedicated stream, e.g. ``fold_in(loop_key, step)`` — see
    ``repro.launch.train``).  ``adversary`` may close over traced leaves, so
    a whole (scenario × α × seed) grid of *training runs* vmaps into one jit
    (:func:`repro.scenarios.train_campaign.run_train_campaign`).

    ``telemetry`` (:class:`repro.obs.TelemetryConfig`, DESIGN.md §12) arms
    the flight recorder: the aggregator runs in probed form and the frame
    joins ``metrics`` under ``tel/``-prefixed keys (per-worker arrays
    included), riding the trainer's existing stacked-metrics flush — no
    ring buffer needed, the chunked scan driver already transfers metrics
    once per ``log_every`` chunk.  Off (the default) leaves the metrics
    schema and trace untouched.
    """
    _validate(cfg, V)
    harness = params_harness(model)
    spec = FlatSpec(harness.d, V, D)
    tel_on = telemetry_on(telemetry)
    _, agg_step = make_aggregator(spec, cfg, telemetry)
    # cast-once-at-ravel (DESIGN.md §5 Numerics): gradient trees ravel
    # straight into the guard's statistics dtype — natively-bf16 LM grads
    # skip the f32 inflation pass entirely under stats_dtype='bf16'.
    # Params/anchor keep the harness dtype: positions feed the optimizer,
    # only the *statistics* ride the precision axis (the guard rounds its
    # own view of delta internally).
    grad_dtype = _grad_dtype(cfg, harness)
    # per-worker-state gates (DESIGN.md §13) — static Python decisions,
    # mirroring run_sgd: no profile (or machinery axis off) lowers to the
    # pre-profile trace, which is the trainer half of the degenerate-
    # WorkerProfile bit-identity guarantee.  The data-skew leg lives in the
    # batch pipeline (make_worker_batch's `skew`), not here.
    profile = getattr(adversary, "profile", None)
    stale_on = profile is not None and cfg.max_delay > 0
    part_on = profile is not None and cfg.partial_participation
    if adversary is None:
        attack_fn = attack_lib.get_attack(cfg.attack)
        attack_kwargs = dict(cfg.attack_kwargs)

    def loss_one(params, tb):
        # named scopes of the step's layers (repro.obs.STEP_SCOPES): HLO
        # metadata only, read from a device trace by perfbench/layers.py
        with jax.named_scope("model/loss"):
            return model.loss_fn(params, tb)

    def train_step(state: TrainState, batch: dict, byz_rank: jax.Array,
                   key: jax.Array):
        k = state.step
        grad_fn = jax.value_and_grad(loss_one, has_aux=True)

        def per_worker(tb):
            (loss, _), g = grad_fn(state.params, tb)
            return loss, g

        losses_w, grads_w = jax.vmap(per_worker)(batch)
        with jax.named_scope("flat/ravel"):
            flat = harness.ravel_workers(grads_w, dtype=grad_dtype)  # (W, d)
            x = harness.ravel(state.params)

        grad_buf = state.grad_buf
        if stale_on:
            # periodic-refresh staleness (run_sgd's model): a straggler's
            # row recomputes only when its schedule fires; between
            # refreshes the carried stale row (a gradient of older params)
            # is what reaches the attack and the aggregation layer
            refresh = adversary.refresh_at(k, cfg.max_delay)
            grad_buf = jnp.where(refresh[:, None], flat, grad_buf)
            flat = grad_buf

        with jax.named_scope("adversary/attack"):
            if adversary is None:
                mask_k = byz_rank < cfg.n_byzantine
            else:
                mask_k = adversary.mask_at(byz_rank, k)
            good_w = (~mask_k).astype(flat.dtype)[:, None]
            honest_mean = (jnp.sum(flat * good_w, axis=0)
                           / jnp.maximum(jnp.sum(good_w), 1.0))
            v_ctx = (jnp.asarray(V, jnp.float32) if V > 0
                     else _estimate_v(flat))   # flat is pre-attack: all honest
            ctx = {
                "true_grad": honest_mean, "V": v_ctx, "step": k,
                "alive": state.prev_alive, "n_alive": state.prev_n_alive,
                "prev_xi": state.prev_xi,
            }
            if adversary is None:
                flat = attack_fn(key, flat, mask_k, ctx, **attack_kwargs)
            else:
                flat = adversary.attack(key, flat, mask_k, ctx, state.adv)

            if part_on:
                # reporting mask ≠ Byzantine mask: honest workers skip steps
                # per p_report, Byzantine workers always deliver (worst case).
                # fold_in leaves the attack's own key stream untouched, so
                # armed machinery with p_report ≡ 1 stays on-trajectory.
                pkey = jax.random.fold_in(key, 7919)
                report = adversary.report_at(pkey, mask_k)
                n_rep = jnp.sum(report).astype(jnp.int32)
            else:
                report = None

        if tel_on:
            guard, xi_flat, n_alive, alive, frame = agg_step(
                state.guard, flat, x, state.anchor, report
            )
        else:
            guard, xi_flat, n_alive, alive = agg_step(
                state.guard, flat, x, state.anchor, report
            )
        adv = state.adv
        if adversary is not None:
            with jax.named_scope("adversary/attack"):
                adv = adversary.update_state(
                    state.adv, mask_k, flat, xi_flat, alive, n_alive, ctx
                )

        with jax.named_scope("flat/unravel"):
            xi_tree = harness.unravel(xi_flat)
        with jax.named_scope("optim/update"):
            updates, opt_state = optimizer.update(
                xi_tree, state.opt_state, state.params, k
            )
            params = tree_add(state.params, updates)

        ever_byz = state.ever_byz | mask_k
        good = (~mask_k).astype(jnp.float32)
        metrics = {
            "loss_good_workers": jnp.sum(losses_w * good)
            / jnp.maximum(jnp.sum(good), 1),
            "loss_all_workers": jnp.mean(losses_w),
            "n_alive": jnp.asarray(n_alive, jnp.int32),
            "good_filtered": jnp.sum((~alive) & (~ever_byz)),
            "byz_alive": jnp.sum(alive & mask_k),
            "n_byz": jnp.sum(mask_k),
            # uniform schema across every aggregator/backend: auto-V-less
            # paths report NaN instead of dropping the key, so stacked
            # campaign metrics and log records never go ragged
            "v_est": (guard.v_est if hasattr(guard, "v_est")
                      else jnp.full((), jnp.nan, jnp.float32)),
            # per-worker-state axis (DESIGN.md §13), same NaN-uniform rule
            "n_reporting": (n_rep.astype(jnp.float32) if part_on
                            else jnp.full((), jnp.nan, jnp.float32)),
        }
        if tel_on:
            # complete the frame with trainer-level signals (the solver's
            # run_sgd convention: 1-based step, ‖ξ‖, adversary feedback)
            frame["step"] = (k + 1).astype(jnp.float32)
            frame["xi_norm"] = jnp.linalg.norm(
                xi_flat.astype(jnp.float32))
            scale = getattr(adv, "adapt_scale", None)
            if scale is not None:
                frame["adapt_scale"] = jnp.asarray(scale, jnp.float32)
            if part_on:
                frame["n_reporting"] = n_rep.astype(jnp.float32)
            if stale_on:
                frame["staleness"] = jnp.mean(
                    adversary.staleness_at(k, cfg.max_delay)
                    .astype(jnp.float32)
                )
            metrics.update({f"tel/{key}": val for key, val in frame.items()})
        new_state = TrainState(
            params=params, opt_state=opt_state, guard=guard,
            anchor=state.anchor, step=k + 1, ever_byz=ever_byz, adv=adv,
            prev_xi=xi_flat.astype(state.prev_xi.dtype), prev_alive=alive,
            prev_n_alive=jnp.asarray(n_alive, jnp.int32),
            grad_buf=grad_buf,
        )
        return new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# serve step (decode shapes)
# ---------------------------------------------------------------------------

def build_serve_step(model: LanguageModel) -> Callable:
    """serve_step(params, cache, tokens (B,1)) → (next_tokens (B,1), cache')."""

    def serve_step(params, cache, tokens):
        logits, cache = model.decode_step(params, cache, tokens)
        nxt = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        return nxt, cache

    return serve_step
