"""Training-path benchmarks (DESIGN.md §10) → ``BENCH_train.json``.

Two deliverables:

1. **scan-vs-loop wall-clock** — the chunked ``lax.scan`` train driver of
   ``repro.launch.train`` against the historical per-step Python loop (one
   jitted call + one host transfer per metric per step).  Both drive the
   *same* jitted ``train_step`` on the same reduced LM, so the comparison
   isolates the driver (dispatch + host-transfer) overhead the scan
   removes.  Steady state excludes the first (compiling) call.

2. **train campaign leaderboard** — ``run_train_campaign`` vmaps a
   (scenario × α × seed) grid of reduced-LM training runs for several
   (aggregator × guard-backend) variants under one jit: does the guard
   still isolate the Byzantine set when the gradients come from a real
   model instead of a convex toy?

Timing hygiene (repo norm, see BENCH_scenarios.json): both deliverables
compare like with like **on the same backend** (scan vs loop run the same
guard; the campaign reports per-variant robustness, not per-backend
speed).  Cross-guard-backend *speed* claims stay with the roofline model
in ``repro.roofline.guard_cost`` — the dp_* backends measured here on CPU
say nothing about TPU wall-clock.

``--mini`` is the CI tier-2 shape: mamba2-130m reduced, 2 guard backends ×
1 scenario (+ mean), ~30 steps.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, write_json
from repro.configs import get_config
from repro.core.solver import SolverConfig, byz_rank
from repro.data.synthetic import SyntheticTokens, make_worker_batch
from repro.distributed.trainer import build_train_step, init_train_state
from repro.models import build_model
from repro.optim import adamw
from repro.scenarios import (
    expand_grid,
    run_train_campaign,
    scenario_adaptive,
    scenario_churn,
    scenario_static,
    summarize_train_campaign,
)
from repro.launch.compile_cache import enable_compile_cache

ARCH = "mamba2-130m"

# CI slack on the scan ≤ loop check: the scan driver removes a *fixed*
# per-step cost, so at the light bench shape its true margin is ~1.2x —
# but back-to-back measurements on a shared CPU box carry enough noise
# to flip a raw ≤ comparison (observed: alternating-round medians still
# land within ±5% on contended runners).  The check therefore asserts
# scan ≤ 1.05 × loop: tight enough to catch a real driver regression
# (which re-adds ≥15% at this shape), loose enough not to flake on noise.
SCAN_LE_LOOP_SLACK = 1.05


def _median_iqr(sorted_times: list[float]) -> tuple[float, float]:
    """(median, IQR) of an already-sorted small sample — the recorded
    round statistics of the alternating-round driver bench."""
    n = len(sorted_times)
    med = sorted_times[n // 2]
    iqr = sorted_times[(3 * n) // 4] - sorted_times[n // 4]
    return med, iqr


def _setup(workers: int, steps: int, seq_len: int, d_model: int,
           guard_backend: str = "dp_exact"):
    cfg = get_config(ARCH).reduced(max_d_model=d_model)
    model = build_model(cfg)
    stream = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=seq_len)
    opt = adamw(3e-3, grad_clip=1.0)
    scfg = SolverConfig(m=workers, T=steps, eta=3e-3, alpha=0.25,
                        aggregator="byzantine_sgd", attack="sign_flip",
                        mean_over_alive=True, guard_backend=guard_backend,
                        guard_opts=(("sketch_dim", 256),))
    return cfg, model, stream, opt, scfg


def scan_vs_loop(workers: int = 8, steps: int = 48, chunk: int = 8,
                 seq_len: int = 16, d_model: int = 32,
                 rounds: int = 3) -> dict:
    """Steady-state per-step wall-clock of the two drivers on the same
    jitted train_step (scan additionally fuses on-device data generation
    into the chunk).

    Timing hygiene: after both paths have compiled, the drivers are timed
    in ``rounds`` *alternating* segments of ``steps`` steps each and the
    per-round median **and IQR** are recorded — back-to-back single
    measurements on a shared CPU box are order-sensitive enough to invert
    a 1.x× margin, and the IQR makes that noise floor visible in the JSON
    instead of silently flipping the ``scan_le_loop`` flag (which itself
    carries the documented ``SCAN_LE_LOOP_SLACK``).
    The default shape is deliberately light (seq 16, d_model 32): the scan
    removes a *fixed* per-step cost (Python dispatch + one host transfer
    per metric), so a compute-heavy step buries the difference in noise —
    at ~30 ms/step the two drivers measure equal on CPU, at ~15 ms/step
    the driver overhead is resolvable.
    """
    cfg, model, stream, opt, scfg = _setup(workers, steps, seq_len, d_model)
    train_step = build_train_step(model, opt, scfg)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    rank = byz_rank(keys[1], workers)
    steps -= steps % chunk

    def make_batch(i):
        return make_worker_batch(stream, workers, 1, i)

    def one_step(st, i):
        return train_step(st, make_batch(i), rank,
                          jax.random.fold_in(keys[3], i))

    step_fn = jax.jit(one_step)

    @jax.jit
    def run_chunk(st, idx):
        return jax.lax.scan(lambda s, i: one_step(s, i), st, idx)

    def time_loop(state, lo):
        # jitted per-step call + per-metric host transfer (the historical
        # driver this bench exists to retire)
        t0 = time.perf_counter()
        for i in range(lo, lo + steps):
            state, m = step_fn(state, jnp.asarray(i))
            _ = {k: float(v) for k, v in m.items()}
        return state, (time.perf_counter() - t0) / steps * 1e6

    def time_scan(state, lo):
        t0 = time.perf_counter()
        for c in range(lo, lo + steps, chunk):
            state, ms = run_chunk(state, jnp.arange(c, c + chunk))
            _ = jax.device_get(ms)
        return state, (time.perf_counter() - t0) / steps * 1e6

    # compile both paths (first calls measured separately)
    state = init_train_state(model, opt, scfg, keys[0])
    t0 = time.perf_counter()
    state, m = step_fn(state, jnp.asarray(0))
    _ = {k: float(v) for k, v in m.items()}
    t_compile_loop = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, ms = run_chunk(state, jnp.arange(1, 1 + chunk))
    _ = jax.device_get(ms)
    t_compile_scan = time.perf_counter() - t0

    loop_times, scan_times = [], []
    lo = 1 + chunk
    for _ in range(rounds):
        state, t = time_loop(state, lo)
        loop_times.append(t)
        lo += steps
        state, t = time_scan(state, lo)
        scan_times.append(t)
        lo += steps
    loop_times.sort(), scan_times.sort()
    loop_us, loop_iqr = _median_iqr(loop_times)
    scan_us, scan_iqr = _median_iqr(scan_times)

    rec = {
        "arch": ARCH, "workers": workers, "steps_per_round": steps,
        "rounds": rounds, "chunk": chunk,
        "seq_len": seq_len, "d_model": d_model,
        "guard_backend": scfg.guard_backend,
        "backend": jax.default_backend(),
        "loop_steady_state_us_per_step": loop_us,
        "scan_steady_state_us_per_step": scan_us,
        "loop_iqr_us": loop_iqr,
        "scan_iqr_us": scan_iqr,
        "loop_us_per_round": loop_times,
        "scan_us_per_round": scan_times,
        "loop_first_call_s": t_compile_loop,
        "scan_first_call_s": t_compile_scan,
        "scan_speedup": loop_us / max(scan_us, 1e-9),
        # the CI check: alternating-round median with the documented noise
        # slack (see SCAN_LE_LOOP_SLACK) — a raw ≤ flips on CPU contention
        "scan_le_loop_slack": SCAN_LE_LOOP_SLACK,
        "scan_le_loop": bool(scan_us <= SCAN_LE_LOOP_SLACK * loop_us),
    }
    emit("train/driver_loop", loop_us, f"steps={steps},rounds={rounds}")
    emit("train/driver_scan", scan_us,
         f"steps={steps},chunk={chunk},speedup={rec['scan_speedup']:.2f}x")
    return rec


def train_campaign(mini: bool, workers: int = 8, steps: int = 30,
                   seq_len: int = 32, d_model: int = 64,
                   backends: list[str] | None = None) -> dict:
    """The (scenario × α × seed) training grid, one jit per the §10 runner."""
    cfg, model, stream, opt, scfg = _setup(workers, steps, seq_len, d_model)
    # attack_scale=2 plays sign_flip at −6g: at the synthetic-LM gradient
    # geometry the default −3g deviation sits only ~14% above the exact
    # 4V radius, a margin the sketch guard's 1.5x threshold slack absorbs
    # by design — the scaled attack separates the backends instead of
    # measuring that known slack (the probe is recorded in DESIGN.md §10's
    # timing-hygiene note and the JSON `note`)
    scenarios = [("static_sign_flip",
                  scenario_static("sign_flip", attack_scale=2.0))]
    if not mini:
        scenarios += [
            ("churn_sign_flip",
             scenario_churn("sign_flip", period=steps // 2,
                            stride=max(workers // 8, 1), attack_scale=2.0)),
            ("adaptive_inner_product",
             scenario_adaptive("inner_product", adapt_rate=0.5)),
        ]
    seeds = range(2) if mini else range(3)
    if backends is None:
        backends = ["dp_exact", "dp_sketch"]
    grid = expand_grid(scenarios, [0.25], seeds)
    result = run_train_campaign(
        model, opt, scfg, grid, steps=steps, stream=stream,
        per_worker_batch=1, aggregators=["mean", "byzantine_sgd"],
        backends=backends,
    )
    record = summarize_train_campaign(result, scfg)
    record["arch"] = ARCH
    record["backends"] = backends
    n_variants = len(result.stats)
    emit("train/campaign", result.wall_s * 1e6,
         f"runs={result.n_runs * n_variants},steps={steps},"
         f"compile_s={result.compile_s:.1f}")
    for row in record["leaderboard"]:
        emit(
            f"train/{row['scenario']}/a{row['alpha']}/{row['variant']}",
            row["loss_final_med"] * 1e6,
            f"loss_final={row['loss_final_med']:.4f},"
            f"byz_alive={row['byz_alive_final_max']},"
            f"good_filtered={row['ever_filtered_good']}",
        )
    return record


def main(mini: bool = False, out_path: str = "BENCH_train.json",
         backends: list[str] | None = None) -> dict:
    steps = 30 if mini else 40
    record = {
        "mini": mini,
        "note": ("scan-vs-loop compares drivers on one backend; "
                 "cross-guard-backend speed uses the roofline model "
                 "(repro.roofline.guard_cost), not CPU wall-clock. "
                 "Campaign sign_flip runs at attack_scale=2 (-6g): the "
                 "default -3g deviation clears the exact 4V radius by only "
                 "~14% at this gradient geometry, inside the dp_sketch "
                 "1.5x threshold slack — the sketch guard absorbing "
                 "marginal attacks is the documented cost of its O(W*k) "
                 "communication, not a leaderboard bug"),
        "driver_wallclock": scan_vs_loop(steps=32 if mini else 48,
                                         rounds=3 if mini else 5),
        "campaign": train_campaign(mini, steps=steps, backends=backends),
    }
    write_json(out_path, record)
    emit("train/report", 0.0, f"out={out_path}")
    return record


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mini", action="store_true",
                    help="CI tier-2 shape: 1 scenario x 2 seeds x 2 backends")
    ap.add_argument("--backends", default=None,
                    help="comma-separated guard backends (default "
                         "dp_exact,dp_sketch)")
    ap.add_argument("--out", default="BENCH_train.json")
    args = ap.parse_args()
    main(mini=args.mini, out_path=args.out,
         backends=args.backends.split(",") if args.backends else None)
