"""Render the scenario-campaign leaderboard from ``BENCH_scenarios.json``
(produced by ``python -m benchmarks.bench_scenarios``) as markdown tables:
which aggregator breaks under which dynamic adversary, the guard's
Theorem-3.8 bound check, detection-latency percentiles, and the
batched-vs-looped wall-clock.

    PYTHONPATH=src python scripts/render_scenarios.py [BENCH_scenarios.json]
"""
from __future__ import annotations

import json
import sys


def _fmt_gap(row: dict) -> str:
    mark = " ✗" if row["breaks"] else ""
    return f"{row['gap_med']:.5f}{mark}"


# mega-campaign grids put hundreds of rows behind every table; render at
# most this many and close the table with a summary footer pointing at
# the JSON record (which always carries the full data)
MAX_TABLE_ROWS = 40


def _us(v) -> str:
    """Modelled µs, or a dash where the run had no chip with a peak row."""
    return "—" if v is None else f"{v:.0f}"


def _cap(rows: list, what: str) -> tuple[list, str | None]:
    """First ``MAX_TABLE_ROWS`` rows + a footer naming how many were cut."""
    if len(rows) <= MAX_TABLE_ROWS:
        return rows, None
    return rows[:MAX_TABLE_ROWS], (
        f"\n… {len(rows) - MAX_TABLE_ROWS} more {what} rows not shown "
        f"({len(rows)} total); see the JSON record for the full table.")


def _guard_bound_lines(guard_bound: list[dict]) -> list[str]:
    lines = []
    lines.append("\n## ByzantineSGD vs the Theorem-3.8 bound\n")
    lines.append("(bound evaluated at the realized ever-Byzantine "
                 "fraction, heterogeneity-adjusted V and effective "
                 "reporter count; one row per guard backend variant; "
                 "`—` marks rows outside the α_ever < 1/2 regime, "
                 "where the theorem makes no claim)\n")
    lines.append("| guard | scenario | α | α_ever | V | m_eff "
                 "| gap med | bound | within |")
    lines.append("|---" * 9 + "|")
    guard_bound, footer = _cap(guard_bound, "guard-bound")
    for g in guard_bound:
        if g.get("in_regime", True):
            mark = "✓" if g["within"] else "✗"
        else:
            mark = "— (α_ever ≥ ½)"
        v = g.get("V_realized")
        m_eff = g.get("m_eff")
        lines.append(
            f"| {g.get('aggregator', 'byzantine_sgd')} "
            f"| {g['scenario']} | {g['alpha']} | {g['alpha_ever']:.3f} "
            f"| {'' if v is None else f'{v:.3f}'} "
            f"| {'' if m_eff is None else f'{m_eff:.1f}'} "
            f"| {g['gap_med']:.5f} | {g['bound']:.4f} "
            f"| {mark} |"
        )
    if footer:
        lines.append(footer)
    return lines


def render(rec: dict) -> str:
    aggs = rec["aggregators"]
    lines = []
    cfg, thr = rec["config"], rec["thresholds"]
    lines.append("## Scenario leaderboard — median f(x̄)−f(x*) across seeds\n")
    lines.append(
        f"m={cfg['m']}, T={cfg['T']}, η={cfg['eta']}; "
        f"✗ = broken (median gap above that α's break threshold); "
        f"{rec['n_runs_per_aggregator']} runs per aggregator, one jit.\n"
    )
    alphas = sorted({r["alpha"] for r in rec["leaderboard"]})
    for alpha in alphas:
        rows = [r for r in rec["leaderboard"] if r["alpha"] == alpha]
        scenarios = sorted({r["scenario"] for r in rows})
        cell = {(r["scenario"], r["aggregator"]): r for r in rows}
        lines.append(f"\n### α = {alpha} "
                     f"(break > {thr[str(alpha)]['break_eps']:.3f})\n")
        lines.append("| scenario | " + " | ".join(aggs) + " |")
        lines.append("|---" * (len(aggs) + 1) + "|")
        for scn in scenarios:
            vals = [_fmt_gap(cell[(scn, a)]) for a in aggs]
            lines.append(f"| {scn} | " + " | ".join(vals) + " |")

    if rec.get("aggregator_ranking"):
        lines.append("\n## Aggregator ranking — mean rank over every "
                     "(scenario × α) cell\n")
        lines.append("| aggregator | mean rank | median gap | worst gap "
                     "| breaks | cells |")
        lines.append("|---" * 6 + "|")
        for r in rec["aggregator_ranking"]:
            lines.append(
                f"| {r['aggregator']} | {r['mean_rank']:.2f} "
                f"| {r['gap_med_median']:.5f} | {r['gap_med_worst']:.5f} "
                f"| {r['n_breaks']} | {r['n_cells']} |"
            )

    if rec.get("degradation"):
        lines.append("\n## Dynamic-vs-static degradation\n")
        lines.append("| aggregator | dynamic | static | α | gap dyn | gap static "
                     "| ratio | degraded |")
        lines.append("|---" * 8 + "|")
        for d in sorted(rec["degradation"],
                        key=lambda d: -d["ratio"])[:12]:
            lines.append(
                f"| {d['aggregator']} | {d['dynamic']} | {d['static']} "
                f"| {d['alpha']} | {d['gap_dynamic']:.5f} "
                f"| {d['gap_static']:.5f} | {d['ratio']:.1f}x "
                f"| {'**yes**' if d['degraded'] else 'no'} |"
            )

    if rec.get("guard_bound"):
        lines.extend(_guard_bound_lines(rec["guard_bound"]))

    het = rec.get("heterogeneous")
    if het:
        lines.append("\n## Heterogeneous slice — per-worker-state profiles "
                     "(DESIGN.md §13)\n")
        lines.append(
            f"profiles: {', '.join(het.get('profiles', []))}; "
            f"max_delay={het.get('max_delay', 0)}; scenario labels carry "
            f"the profile suffix; {het['n_runs_per_aggregator']} runs per "
            f"aggregator, one jit.\n"
        )
        lines.append("| scenario | aggregator | gap med | detect p50 "
                     "| ever filtered good |")
        lines.append("|---" * 5 + "|")
        het_rows, het_footer = _cap(het["leaderboard"], "heterogeneous")
        for r in het_rows:
            lines.append(
                f"| {r['scenario']} | {r['aggregator']} "
                f"| {r['gap_med']:.5f} | {r['detect_p50']} "
                f"| {'yes' if r['ever_filtered_good'] else 'no'} |"
            )
        if het_footer:
            lines.append(het_footer)
        if het.get("guard_bound"):
            lines.extend(_guard_bound_lines(het["guard_bound"]))

    mega = rec.get("mega")
    if mega and mega.get("grid"):
        g = mega["grid"]
        lines.append("\n## Mega campaign — chunked 10× grid (DESIGN.md §14)\n")
        ratio = g.get("peak_temp_ratio_vs_reference")
        bounded = g.get("peak_memory_bounded")
        lines.append(
            f"{g['total_runs']} runs ({g['n_runs']} grid rows × "
            f"{g['n_variants']} variants, T={g['T']}) under one traced "
            f"campaign: `lax.map` over {g['n_chunks']} chunks of "
            f"{g['chunk_size']}; backends: {', '.join(g['backends'])}.\n"
        )
        if ratio is not None:
            lines.append(
                f"peak temp memory vs the {g['reference_runs']}-run "
                f"unchunked reference: {ratio:.2f}× "
                f"({'✓ bounded' if bounded else '✗ NOT bounded'}, "
                f"assertion ≤ 2×); wall {g['wall_s']:.1f}s "
                f"+ {g['compile_s']:.1f}s compile.\n"
            )
        if mega.get("aggregator_ranking"):
            lines.append("| aggregator | mean rank | median gap | worst gap "
                         "| breaks | cells |")
            lines.append("|---" * 6 + "|")
            for r in mega["aggregator_ranking"]:
                lines.append(
                    f"| {r['aggregator']} | {r['mean_rank']:.2f} "
                    f"| {r['gap_med_median']:.5f} "
                    f"| {r['gap_med_worst']:.5f} "
                    f"| {r['n_breaks']} | {r['n_cells']} |"
                )
        if mega.get("guard_bound"):
            lines.extend(_guard_bound_lines(mega["guard_bound"]))

    lines.append("\n## Detection latency (ByzantineSGD), steps to full filter\n")
    lines.append("| guard | scenario | α | p50 | p90 | detect rate |")
    lines.append("|---" * 6 + "|")
    lat_rows, lat_footer = _cap(
        [r for r in rec["leaderboard"]
         if r["aggregator"].startswith("byzantine_sgd")], "detection-latency")
    for r in lat_rows:
        lines.append(f"| {r['aggregator']} | {r['scenario']} | {r['alpha']} "
                     f"| {r['detect_p50']} | {r['detect_p90']} "
                     f"| {r['detect_rate']:.2f} |")
    if lat_footer:
        lines.append(lat_footer)

    ba = rec.get("backend_axis")
    if ba:
        shape = ba["model_shape"]
        lines.append("\n## Guard-backend axis (DESIGN.md §9)\n")
        lines.append(
            f"measured on `{ba['measured_backend']}` "
            f"(fused via Pallas interpreter: {ba['fused_runs_interpret']}); "
            f"model = bytes/HBM-bandwidth on {shape['hw'] or 'no chip'} at "
            f"m={shape['m']}, d={shape['d']}.\n"
        )
        lines.append("| backend | campaign wall s | runs | model step bytes "
                     "| model steady-state µs |")
        lines.append("|---" * 5 + "|")
        for be, p in ba["per_backend"].items():
            lines.append(
                f"| {be} | {p['campaign_wall_s']:.2f} | {p['campaign_runs']} "
                f"| {p['model_step_bytes']:,} "
                f"| {_us(p['model_steady_state_us'])} |"
            )
        if "fused_le_dense_model" in ba:
            lines.append(
                f"\nfused ≤ dense at the headline shape (model): "
                f"{'✓' if ba['fused_le_dense_model'] else '✗'}"
            )

    wc = rec["wall_clock"]
    lines.append(
        f"\ncampaign wall-clock: {wc['runs_total']} runs in "
        f"{wc['batched_s']:.2f}s (one jit; +{wc['compile_s']:.1f}s compile)"
    )
    mx = rec.get("matrix6x6_wallclock")
    if mx and "looped_s" in mx:
        lines.append(
            f"\n6×6 matrix (T={mx['T']}): batched {mx['batched_s']:.2f}s vs "
            f"looped {mx['looped_s']:.2f}s → "
            f"{mx['speedup_steady']:.1f}x steady-state "
            f"({mx['speedup_incl_compile']:.2f}x incl. compile)"
        )
    return "\n".join(lines)


def main() -> None:
    path = sys.argv[1] if len(sys.argv) > 1 else "BENCH_scenarios.json"
    with open(path) as f:
        rec = json.load(f)
    print(render(rec))


if __name__ == "__main__":
    main()
