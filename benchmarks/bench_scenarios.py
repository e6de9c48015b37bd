"""Scenario campaigns — dynamic adversaries × aggregators, one jit.

Two deliverables (DESIGN.md §8):

1. the **scenario leaderboard**: every aggregator against the full dynamic
   zoo (lie-low-then-strike, churn, coalition splits, filter-feedback
   adaptation) across ≥ 100 (scenario, α, seed) grid rows, seed-aggregated
   into ``BENCH_scenarios.json`` — including the degradation table (which
   baselines break under a dynamic adversary whose static counterpart they
   survive) and the Theorem-3.8 bound check for the guard;
2. the **batched-vs-looped wall-clock** on the 6×6 robustness matrix: the
   one-jit campaign against the historical one-eager-``run_sgd``-per-cell
   Python loop.

Third deliverable (DESIGN.md §9): the **guard-backend axis** — the same
campaign sweeps the guard's realizations (dense / fused Pallas pipeline /
distributed CountSketch) as variants next to the aggregator axis, and the
report gains a ``backend_axis`` section with per-backend campaign
wall-clock (measured on this backend) plus the roofline-model steady-state
per-step wall-clock at the m = 32, d = 2²⁰ headline shape, where the fused
pipeline's 3-vs-6-pass traffic reduction makes it strictly cheaper than
dense.

Fourth deliverable (DESIGN.md §12): ``--trace-out`` arms the guard
**flight recorder** on a guard-only rerun of the campaign — per-step
filter forensics for the adaptive cells (martingale deviations vs
thresholds, alive deltas, first-filter steps) exported as structured
JSONL + a Perfetto-loadable chrome trace, with the measured
telemetry-enabled overhead fraction recorded in the trace's own meta
block, and measured-vs-roofline comparator rows for the swept backends.

Fifth deliverable (DESIGN.md §13): the **heterogeneous slice** — non-iid
data skew, periodic stragglers, and partial participation swept as a named
:class:`~repro.scenarios.spec.WorkerProfile` axis of one campaign (the
``heterogeneous`` record section), with the Theorem-3.8 check at each
row's realized skew-inflated V and effective reporter count.

Sixth deliverable (DESIGN.md §14): the **mega campaign** — the full
(scenario × α × seed) grid 10×'d to tens of thousands of runs under ONE
traced campaign, peak device memory bounded by run-axis chunking
(``lax.map`` over chunks of the vmapped grid) and the ``gen``
pseudo-backend regenerating worker gradients inside the guard sweep so
the (N, m, d) batch never materializes.  The record carries the compiled
program's memory analysis next to a chunk-sized reference compile and
*asserts* the chunked temp allocation stays within 2× of it — the
sublinear-in-runs peak-memory claim lives in the artifact it gates.

``--mini`` is the CI tier-2 shape: 5 scenarios (3 dynamic) × 2 seeds at
small T, the guard backends (gen included), one non-iid skew level in the
heterogeneous slice, looped comparison on the matrix kept, and a
guard-only ~2k-run mini-mega grid with the peak-memory assertion.
"""
from __future__ import annotations

import argparse
import time

import jax

from benchmarks.common import chip_peaks, emit
from repro.core.guard_backends import parse_backend_spec
from repro.core.solver import SolverConfig
from repro.data.problems import (
    heterogenize_problem,
    make_generated_problem,
    make_quadratic_problem,
)
from repro.kernels import ops
from repro.obs import EventLog, TelemetryConfig, roofline_rows
from repro.roofline.guard_cost import backend_cost, steady_state_us
from repro.scenarios import (
    degraded_pairs,
    expand_grid,
    profile_iid,
    profile_partial,
    profile_stragglers,
    run_campaign,
    run_campaign_looped,
    scenario_adaptive,
    scenario_churn,
    scenario_coalition,
    scenario_lie_low_then_strike,
    scenario_static,
    summarize_campaign,
    worker_profile,
    write_report,
)
from repro.scenarios.campaign import CampaignResult, build_campaign_fn
from repro.scenarios.report import campaign_trace_events, filter_timelines
from repro.launch.compile_cache import enable_compile_cache

# the blades-comparable aggregator cross: the classical zoo, the stateful
# rules (AutoGM's auto-weighted geometric median, Karimireddy's
# momentum-carried centered clipping), two bucketing compositions
# (s=2 pre-averaging in front of Krum / trimmed mean), and the guard
AGGREGATORS = ["mean", "krum", "coordinate_median", "trimmed_mean",
               "geometric_median", "autogm", "centered_clip",
               "bucket2:krum", "bucket2:trimmed_mean", "byzantine_sgd"]
MATRIX_ATTACKS = ["none", "sign_flip", "random_gaussian", "alie",
                  "inner_product", "hidden_shift"]
# the guard-backend sweep: dense oracle, fused Pallas pipeline at both
# statistics precisions (DESIGN.md §5 Numerics — the bf16 row records the
# accuracy cost of the halved guard traffic), the in-kernel-generation
# pseudo-backend (DESIGN.md §14 — fused + generate='kernel', worker
# strips regenerated inside the sweep), distributed CountSketch guard
# (dp_exact is covered by the tier-1 parity tests; it models collective
# savings, not local-traffic savings, so the leaderboard sweeps the local
# realizations)
BACKENDS = ["dense", "fused", "fused@bf16", "gen", "dp_sketch"]
MINI_BACKENDS = ["dense", "fused", "fused@bf16", "gen"]
# headline shape of the DESIGN.md §5 roofline claim
MODEL_SHAPE = {"m": 32, "d": 1 << 20}
# run-axis chunk width of the mega campaign (DESIGN.md §14): peak device
# memory scales with this, not with the grid's tens of thousands of runs
MEGA_CHUNK = 120


def scenario_zoo(T: int, m: int) -> tuple[list, dict]:
    """The standard campaign scenarios + the dynamic→static pairing used by
    the degradation table.  Churn is one rotation by an m/8-sized group at
    T/2, so the ever-Byzantine fraction is α + 1/8 — at most 0.375 on the
    α ≤ 0.25 grid, strictly inside the α < 1/2 Theorem-3.8 regime (the
    report checks the bound at that realized fraction)."""
    scenarios = [
        ("static_sign_flip", scenario_static("sign_flip")),
        ("static_alie", scenario_static("alie")),
        ("static_alie_update", scenario_static("alie_update")),
        ("static_inner_product", scenario_static("inner_product")),
        ("static_hidden_shift", scenario_static("hidden_shift")),
        ("lie_low_then_strike", scenario_lie_low_then_strike("inner_product", T // 2)),
        ("churn_sign_flip", scenario_churn("sign_flip", period=T // 2, stride=m // 8)),
        ("adaptive_inner_product", scenario_adaptive("inner_product", adapt_rate=0.5)),
        ("coalition_alie_ip", scenario_coalition("alie", "inner_product", 0.5)),
        ("retreat_on_filter", scenario_static("retreat_on_filter")),
    ]
    static_of = {
        "lie_low_then_strike": "static_inner_product",
        "churn_sign_flip": "static_sign_flip",
        "adaptive_inner_product": "static_inner_product",
        "coalition_alie_ip": "static_alie",
        "retreat_on_filter": "static_inner_product",
    }
    return scenarios, static_of


def campaign_leaderboard(mini: bool, backends: list[str] | None = None) -> dict:
    m = 16
    T = 300 if mini else 1500
    # generated problem (counter-based PRNG sampler, DESIGN.md §14): the
    # same worker-gradient distribution whether rows are materialized on
    # the host (dense/fused/dp_sketch variants) or regenerated inside the
    # guard sweep (the "gen" variant) — one leaderboard, all realizations
    prob = make_generated_problem(d=16, sigma=1.0, L=8.0, V=1.0, seed=0)
    # sketch_dim < d so the dp_sketch variant actually exercises sketch
    # compression (k=8 at d=16 is a 2x fold; the default k=4096 > d would
    # make the CountSketch lossless and silently measure the exact guard);
    # the opts filter drops the knob for the dense/fused variants
    cfg = SolverConfig(m=m, T=T, eta=0.05, alpha=0.25,
                       aggregator="byzantine_sgd", attack="sign_flip",
                       guard_opts=(("sketch_dim", 8),))
    scenarios, static_of = scenario_zoo(T, m)
    aggs = AGGREGATORS
    if mini:
        keep = {"static_sign_flip", "static_inner_product",
                "lie_low_then_strike", "churn_sign_flip",
                "adaptive_inner_product"}
        scenarios = [s for s in scenarios if s[0] in keep]
        static_of = {k: v for k, v in static_of.items() if k in keep}
        alphas, seeds = [0.25], range(2)
        aggs = ["mean", "krum", "autogm", "centered_clip", "byzantine_sgd"]
    else:
        alphas, seeds = [0.125, 0.25], range(8)
    if backends is None:
        backends = MINI_BACKENDS if mini else BACKENDS

    grid = expand_grid(scenarios, alphas, seeds)
    result = run_campaign(prob, cfg, grid, aggs, backends=backends)
    record = summarize_campaign(result, prob, cfg, static_of=static_of)
    record["backend_axis"] = backend_axis_record(prob, cfg, grid, backends)
    n_variants = len(result.stats)
    emit("scenarios/campaign", result.wall_s * 1e6,
         f"runs={result.n_runs * n_variants},backends={len(backends)},"
         f"compile_s={result.compile_s:.1f}")
    for row in record["leaderboard"]:
        emit(
            f"scenarios/{row['scenario']}/a{row['alpha']}/{row['aggregator']}",
            row["gap_med"] * 1e6,  # gap in µ-units for the CSV column
            f"gap_med={row['gap_med']:.5f},detect_p50={row['detect_p50']},"
            f"breaks={row['breaks']}",
        )
    for row in record["guard_bound"]:
        # one row per guard backend variant — the variant is part of the key
        emit(f"scenarios/bound/{row['aggregator']}/{row['scenario']}"
             f"/a{row['alpha']}",
             row["gap_med"] * 1e6,
             f"thm38_bound={row['bound']:.4f},within={row['within']},"
             f"alpha_ever={row['alpha_ever']:.3f}")
    for row in degraded_pairs(record):
        emit(f"scenarios/degraded/{row['aggregator']}/{row['dynamic']}",
             row["gap_dynamic"] * 1e6,
             f"static_gap={row['gap_static']:.5f},ratio={row['ratio']:.1f}")
    return record


def _slice_grid(grid, n: int):
    """First-``n``-rows view of a stacked grid — the chunk-sized reference
    compile of the mega campaign's peak-memory assertion."""
    from repro.scenarios.spec import CampaignGrid
    return CampaignGrid(
        jax.tree.map(lambda x: x[:n], grid.scenarios),
        grid.alpha[:n], grid.seeds[:n], grid.entries[:n],
        None if grid.profiles is None
        else jax.tree.map(lambda x: x[:n], grid.profiles),
    )


def mega_campaign(mini: bool, backends: list[str] | None = None,
                  chunk_size: int = MEGA_CHUNK) -> dict:
    """The 10×-grid deliverable (DESIGN.md §14): the full scenario zoo ×
    a dense α grid × a deep seed axis, under ONE traced chunked campaign.

    Full shape: 10 scenarios × 6 α × 16 seeds = 960 grid rows × 14
    variants (every aggregator, the guard expanded across all five
    backend realizations, in-kernel generation included) = 13 440 runs.
    Mini (CI tier-2): guard-only, 10 × 4 α × 12 seeds × 4 backends =
    1 920 runs at small T.

    Peak memory is the point: the chunked campaign's XLA temp allocation
    is compared against a *chunk-sized reference grid* compiled unchunked
    — the assertion that the mega grid's temp bytes stay ≤ 2× the
    reference is what "peak memory sublinear in runs" means, and it fails
    the benchmark loudly rather than decorating it.
    """
    m = 16
    T = 100 if mini else 1500
    prob = make_generated_problem(d=16, sigma=1.0, L=8.0, V=1.0, seed=0)
    cfg = SolverConfig(m=m, T=T, eta=0.05, alpha=0.25,
                       aggregator="byzantine_sgd", attack="sign_flip",
                       guard_opts=(("sketch_dim", 8),))
    scenarios, static_of = scenario_zoo(T, m)
    if mini:
        alphas, seeds = [0.0625, 0.125, 0.1875, 0.25], range(12)
        aggs: list[str] = ["byzantine_sgd"]
        static_of = None
    else:
        alphas = [0.0625, 0.125, 0.1875, 0.25, 0.3125, 0.375]
        seeds = range(16)
        aggs = AGGREGATORS
    if backends is None:
        backends = MINI_BACKENDS if mini else BACKENDS

    grid = expand_grid(scenarios, alphas, list(seeds))
    result = run_campaign(prob, cfg, grid, aggs, backends=backends,
                          chunk_size=chunk_size)
    ref_n = min(chunk_size, grid.n_runs)
    ref = run_campaign(prob, cfg, _slice_grid(grid, ref_n), aggs,
                       backends=backends)

    record = summarize_campaign(result, prob, cfg, static_of=static_of)
    n_variants = len(result.stats)
    total_runs = grid.n_runs * n_variants
    peak_ratio = peak_bounded = None
    if result.memory and ref.memory:
        peak_ratio = (result.memory["temp_size_in_bytes"]
                      / max(ref.memory["temp_size_in_bytes"], 1))
        peak_bounded = bool(peak_ratio <= 2.0)
    record["grid"] = {
        "n_runs": grid.n_runs,
        "n_variants": n_variants,
        "total_runs": total_runs,
        "chunk_size": chunk_size,
        "n_chunks": -(-grid.n_runs // chunk_size),
        "T": T,
        "backends": list(backends),
        "wall_s": result.wall_s,
        "compile_s": result.compile_s,
        "memory": result.memory,
        "reference_runs": ref_n,
        "reference_memory": ref.memory,
        "peak_temp_ratio_vs_reference": peak_ratio,
        "peak_memory_bounded": peak_bounded,
    }
    emit("scenarios/mega_campaign", result.wall_s * 1e6,
         f"runs={total_runs},chunks={record['grid']['n_chunks']},"
         f"chunk_size={chunk_size},compile_s={result.compile_s:.1f},"
         f"peak_temp_ratio={peak_ratio if peak_ratio is None else round(peak_ratio, 3)},"
         f"bounded={peak_bounded}")
    if peak_bounded is False:
        raise SystemExit(
            f"mega campaign peak-memory assertion failed: chunked temp "
            f"bytes {result.memory['temp_size_in_bytes']} exceed 2x the "
            f"{ref_n}-run reference's {ref.memory['temp_size_in_bytes']}")
    return record


def heterogeneous_campaign(mini: bool,
                           backends: list[str] | None = None) -> dict:
    """The per-worker-state slice (DESIGN.md §13): non-iid data skew,
    periodic stragglers, and partial participation as a *named profile
    axis* of one campaign — every row, the armed-degenerate ``uniform``
    profile included, stacks into the same single ``jit(vmap)`` trace.

    Runs on a heterogenized problem (known optimum, zero-sum per-worker
    bias directions), so the report's Theorem-3.8 check evaluates each
    row's bound at its *realized* skew-inflated V and effective reporter
    count rather than the worst case the problem's V was built for.
    """
    m = 16
    T = 300 if mini else 1500
    max_delay = 3
    # one skew level for CI; the full sweep adds a second
    skews = [0.5] if mini else [0.25, 0.5]
    prob = heterogenize_problem(
        make_quadratic_problem(d=16, sigma=1.0, L=8.0, V=1.0, seed=0),
        m, skew_max=max(skews), seed=0,
    )
    cfg = SolverConfig(m=m, T=T, eta=0.05, alpha=0.25,
                       aggregator="byzantine_sgd", attack="sign_flip",
                       max_delay=max_delay, partial_participation=True)
    keep = {"static_sign_flip", "churn_sign_flip"}
    scenarios = [s for s in scenario_zoo(T, m)[0] if s[0] in keep]
    # fleet-uniform skew keeps the per-worker biases cancelling exactly,
    # so the known optimum (and hence the bound's gap) stays valid
    profiles = [("uniform", profile_iid(m))]
    profiles += [(f"skew{s:g}", worker_profile(m, skew=s)) for s in skews]
    profiles += [("stragglers", profile_stragglers(m, 0.25, max_delay)),
                 ("partial", profile_partial(m, 0.8))]
    seeds = range(2) if mini else range(4)
    grid = expand_grid(scenarios, [0.25], seeds, profiles=profiles)
    aggs = ["mean", "byzantine_sgd"]
    if backends is None:
        backends = ["dense"] if mini else ["dense", "fused"]
    result = run_campaign(prob, cfg, grid, aggs, backends=backends)
    record = summarize_campaign(result, prob, cfg)
    record["profiles"] = [name for name, _ in profiles]
    record["max_delay"] = max_delay
    n_variants = len(result.stats)
    emit("scenarios/het_campaign", result.wall_s * 1e6,
         f"runs={result.n_runs * n_variants},profiles={len(profiles)},"
         f"compile_s={result.compile_s:.1f}")
    for row in record["guard_bound"]:
        emit(f"scenarios/het_bound/{row['aggregator']}/{row['scenario']}"
             f"/a{row['alpha']}",
             row["gap_med"] * 1e6,
             f"thm38_bound={row['bound']:.4f},within={row['within']},"
             f"V_realized={row['V_realized']:.3f},"
             f"alpha_ever={row['alpha_ever']:.3f},"
             f"in_regime={row['in_regime']}")
    return record


def backend_axis_record(prob, cfg, grid, backends: list[str]) -> dict:
    """Per-backend record: measured steady-state campaign wall-clock (each
    backend's guard-only campaign, compiled separately so the execution time
    is attributable) + the roofline-model per-step steady-state wall-clock
    at the m = 32, d = 2²⁰ headline shape on the chip the run is on (no
    modelled time off the TPU).

    On CPU the fused backend runs the Pallas *interpreter*, so its measured
    numbers are not comparable across backends (``interpret`` is recorded);
    the modeled numbers are the cross-backend comparison — bytes moved is
    wall-clock for this memory-bound step, and the fused pipeline's 3-pass
    sweep is strictly cheaper than the dense 6-pass reference.
    """
    ms, ds = MODEL_SHAPE["m"], MODEL_SHAPE["d"]
    hw = chip_peaks()
    per_backend = {}
    for be in backends:
        timed = run_campaign(prob, cfg, grid, ["byzantine_sgd"],
                             backends=[be])
        name, sdt = parse_backend_spec(be)
        cost = backend_cost(name, ms, ds, sdt or "f32")
        per_backend[be] = {
            "campaign_wall_s": timed.wall_s,
            "campaign_compile_s": timed.compile_s,
            "campaign_runs": timed.n_runs,
            "stats_dtype": sdt or "f32",
            "model_stats_bytes": cost.stats_bytes,
            "model_step_bytes": cost.step_bytes,
            "model_steady_state_us": (steady_state_us(cost, hw)
                                      if hw is not None else None),
        }
        model_us = per_backend[be]["model_steady_state_us"]
        emit(f"scenarios/backend/{be}", timed.wall_s * 1e6,
             f"runs={timed.n_runs},"
             f"model_step_bytes_m{ms}_d2e20={cost.step_bytes}"
             + (f",model_step_us_m{ms}_d2e20={model_us:.0f}"
                if model_us is not None else ""))
    rec = {
        "backends": backends,
        "guard_opts": dict(cfg.guard_opts),
        "model_shape": dict(MODEL_SHAPE,
                            hw=hw.name if hw is not None else None,
                            hbm_bw=hw.hbm_bw if hw is not None else None,
                            source="repro.roofline.guard_cost"),
        "measured_backend": jax.default_backend(),
        "fused_runs_interpret": ops.interpret_mode(),
        "per_backend": per_backend,
    }
    if "dense" in per_backend and "fused" in per_backend:
        # bandwidth-bound model: the byte order is the time order
        rec["fused_le_dense_model"] = bool(
            per_backend["fused"]["model_step_bytes"]
            <= per_backend["dense"]["model_step_bytes"]
        )
    if "fused" in per_backend and "fused@bf16" in per_backend:
        # the ISSUE-5 headline: bf16 statistics move ≤ 0.55x the f32 bytes
        rec["bf16_stats_ratio_model"] = (
            per_backend["fused@bf16"]["model_stats_bytes"]
            / per_backend["fused"]["model_stats_bytes"]
        )
    return rec


def _timed_campaign(prob, cfg, grid, backends, telemetry, reps: int = 3):
    """Lower once, execute ``reps`` times, keep the min wall — the
    overhead comparison needs execution-only times robust to scheduler
    noise at the mini shape, which single-shot ``run_campaign`` is not."""
    fn = jax.jit(build_campaign_fn(prob, cfg, ["byzantine_sgd"],
                                   backends=backends, telemetry=telemetry))
    t0 = time.perf_counter()
    compiled = fn.lower(grid).compile()
    compile_s = time.perf_counter() - t0
    walls, out = [], None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(grid))
        walls.append(time.perf_counter() - t0)
    return CampaignResult(stats=out, entries=grid.entries,
                          wall_s=min(walls), compile_s=compile_s,
                          n_runs=grid.n_runs)


def trace_campaign(mini: bool, trace_out: str,
                   backends: list[str] | None = None,
                   ring_size: int = 64) -> dict:
    """The flight-recorder deliverable (DESIGN.md §12): a guard-only rerun
    of the leaderboard campaign, telemetry off vs on.

    Off/on wall-clocks give the measured enabled-mode overhead (recorded
    in the trace meta — the ≤10 % acceptance bound lives *in* the
    artifact it gates); the armed run's rings are drained into guard_step
    events for the dynamic cells, roofline comparator rows join each
    backend's measured per-step time against the ``guard_cost`` model at
    the campaign shape, and both JSONL and a Perfetto-loadable chrome
    trace are written next to ``BENCH_scenarios.json``.
    """
    m, d = 16, 16
    T = 300 if mini else 1500
    # heterogenized problem + armed per-worker-state gates: the traced
    # cells sweep a uniform profile next to a mixed skew/straggler/partial
    # one, so the exported frames exercise the n_reporting / staleness
    # lanes of the schema (DESIGN.md §13)
    prob = heterogenize_problem(
        make_quadratic_problem(d=d, sigma=1.0, L=8.0, V=1.0, seed=0),
        m, skew_max=0.3, seed=0,
    )
    cfg = SolverConfig(m=m, T=T, eta=0.05, alpha=0.25,
                       aggregator="byzantine_sgd", attack="sign_flip",
                       guard_opts=(("sketch_dim", 8),),
                       max_delay=2, partial_participation=True)
    scenarios, _ = scenario_zoo(T, m)
    keep = {"static_sign_flip", "adaptive_inner_product",
            "lie_low_then_strike"}
    scenarios = [s for s in scenarios if s[0] in keep]
    profiles = [
        ("uniform", profile_iid(m)),
        ("hetmix", worker_profile(m, skew=0.3, p_report=0.9)._replace(
            delay=profile_stragglers(m, 0.25, 2).delay)),
    ]
    grid = expand_grid(scenarios, [0.25], range(2), profiles=profiles)
    if backends is None:
        backends = ["dense", "fused"]
    tel = TelemetryConfig(enabled=True, ring_size=ring_size)

    log = EventLog(tool="benchmarks.bench_scenarios", mini=mini,
                   m=m, d=d, T=T, ring_size=ring_size,
                   grid_runs=grid.n_runs, backends=list(backends))
    measured_step_us: dict[str, float] = {}
    off_wall = on_wall = 0.0
    n_cells = 0
    dynamic = ("adaptive_inner_product", "lie_low_then_strike")
    results_on = {}
    for be in backends:
        off = _timed_campaign(prob, cfg, grid, [be], None)
        on = _timed_campaign(prob, cfg, grid, [be], tel)
        off_wall += off.wall_s
        on_wall += on.wall_s
        measured_step_us[be] = off.wall_s / (off.n_runs * T) * 1e6
        n_cells += campaign_trace_events(
            on, log, select=lambda e: e["scenario"] in dynamic)
        results_on[be] = on
    overhead = on_wall / max(off_wall, 1e-9) - 1.0
    hw = chip_peaks()
    if hw is not None:
        for row in roofline_rows(measured_step_us, m, d, hw):
            log.event("roofline", **row)
    timelines = [r for be in backends
                 for r in filter_timelines(results_on[be])]
    log.add_meta(telemetry_overhead_frac=overhead,
                 telemetry_off_wall_s=off_wall,
                 telemetry_on_wall_s=on_wall)
    log.write_jsonl(trace_out)
    perfetto = trace_out.rsplit(".", 1)[0] + ".perfetto.json"
    log.write_chrome_trace(perfetto)
    emit("scenarios/telemetry_overhead", overhead * 1e6,
         f"off_s={off_wall:.3f},on_s={on_wall:.3f},cells={n_cells},"
         f"out={trace_out}")
    return {
        "trace_path": trace_out,
        "perfetto_path": perfetto,
        "overhead_frac": overhead,
        "off_wall_s": off_wall,
        "on_wall_s": on_wall,
        "cells_exported": n_cells,
        "events": len(log.events),
        "filter_timelines": timelines,
    }


def matrix_wallclock(mini: bool, skip_looped: bool = False) -> dict:
    """The 6×6 robustness matrix (every static attack × every aggregator),
    batched through one jit vs the historical per-cell Python loop."""
    m = 16
    T = 200 if mini else 2000
    prob = make_quadratic_problem(d=16, sigma=1.0, L=8.0, V=1.0, seed=0)
    cfg = SolverConfig(m=m, T=T, eta=0.05, alpha=0.25,
                       aggregator="byzantine_sgd", attack="sign_flip")
    scenarios = [(a, scenario_static(a)) for a in MATRIX_ATTACKS]
    grid = expand_grid(scenarios, [0.25], [0])
    result = run_campaign(prob, cfg, grid, AGGREGATORS)
    cells = result.n_runs * len(AGGREGATORS)
    rec = {
        "T": T,
        "cells": cells,
        "batched_s": result.wall_s,
        "batched_compile_s": result.compile_s,
    }
    if not skip_looped:
        _, looped_s = run_campaign_looped(prob, cfg, grid, AGGREGATORS)
        rec["looped_s"] = looped_s
        rec["speedup_steady"] = looped_s / max(result.wall_s, 1e-9)
        rec["speedup_incl_compile"] = looped_s / max(
            result.wall_s + result.compile_s, 1e-9
        )
    emit("scenarios/matrix6x6_batched", result.wall_s * 1e6,
         f"cells={cells},compile_s={result.compile_s:.1f}")
    if not skip_looped:
        emit("scenarios/matrix6x6_looped", looped_s * 1e6,
             f"cells={cells},speedup_steady={rec['speedup_steady']:.1f}x,"
             f"incl_compile={rec['speedup_incl_compile']:.2f}x")
    return rec


def main(mini: bool = False, skip_looped: bool = False,
         out_path: str = "BENCH_scenarios.json",
         backends: list[str] | None = None,
         trace_out: str | None = None) -> dict:
    record = campaign_leaderboard(mini, backends=backends)
    record["mega"] = mega_campaign(mini, backends=backends)
    record["heterogeneous"] = heterogeneous_campaign(mini)
    record["matrix6x6_wallclock"] = matrix_wallclock(mini, skip_looped)
    record["mini"] = mini
    if trace_out:
        record["telemetry"] = trace_campaign(mini, trace_out)
    write_report(record, out_path)
    emit("scenarios/report", 0.0,
         f"out={out_path},degraded_pairs={len(degraded_pairs(record))}")
    return record


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mini", action="store_true",
                    help="CI tier-2 shape: 5 scenarios x 2 seeds, small T")
    ap.add_argument("--skip-looped", action="store_true",
                    help="skip the slow per-cell Python-loop baseline")
    ap.add_argument("--backends", default=None,
                    help="comma-separated guard backends to sweep "
                         f"(default: {','.join(MINI_BACKENDS)} for --mini, "
                         f"{','.join(BACKENDS)} otherwise)")
    ap.add_argument("--out", default="BENCH_scenarios.json")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="arm the guard flight recorder on a guard-only "
                         "campaign rerun and write the JSONL event log + "
                         "Perfetto trace here (DESIGN.md §12)")
    args = ap.parse_args()
    main(mini=args.mini, skip_looped=args.skip_looped, out_path=args.out,
         backends=args.backends.split(",") if args.backends else None,
         trace_out=args.trace_out)
