"""Table 1 — sample complexity: iterations-to-ε per aggregator / α / m.

The paper's headline claims, measured:
  * mini-batch SGD (mean) matches ByzantineSGD at α = 0 (criterion 3);
  * under attack, mean diverges while ByzantineSGD's T-to-ε degrades only
    by the additive α² term;
  * parallel speedup: T-to-ε improves with m (Remark 1.2).

Every point is now a ≥ 5-seed distribution (median + IQR), not a single
run: the seeds ride a campaign grid (repro.scenarios.campaign), so each
sweep is one jit(vmap) instead of a Python loop of re-traced solves.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit
from repro.core.solver import SolverConfig
from repro.data.problems import make_quadratic_problem
from repro.scenarios import expand_grid, run_campaign, scenario_static
from repro.launch.compile_cache import enable_compile_cache

SEEDS = range(5)


def iters_to_eps_batch(gaps: np.ndarray, eps: float) -> np.ndarray:
    """First iteration (1-based) whose running-min gap is ≤ eps, per run;
    -1 where the run never reaches eps.  ``gaps`` is (N, T)."""
    below = np.minimum.accumulate(np.asarray(gaps), axis=1) <= eps
    hit = below.any(axis=1)
    return np.where(hit, below.argmax(axis=1) + 1, -1)


def _emit_quantiles(name: str, t: np.ndarray) -> None:
    ok = t[t > 0]
    if ok.size == 0:
        emit(name, -1.0, f"iters_to_eps_med=-1,n_seeds={t.size},reached=0")
        return
    p25, med, p75 = np.percentile(ok, [25, 50, 75])
    emit(name, float(med),
         f"iters_to_eps_med={int(med)},iqr=[{int(p25)},{int(p75)}],"
         f"reached={ok.size}/{t.size}")


def main() -> None:
    prob = make_quadratic_problem(d=16, sigma=1.0, L=8.0, V=1.0, seed=0)
    eps = 2e-2
    T = 4000

    # --- α = 0: guard matches mean (one campaign, both aggregators) ---
    cfg = SolverConfig(m=16, T=T, eta=0.05, alpha=0.0,
                       aggregator="mean", attack="none")
    grid = expand_grid([("none", scenario_static("none"))], [0.0], SEEDS)
    res = run_campaign(prob, cfg, grid, ["mean", "byzantine_sgd"],
                       return_gaps=True)
    for agg in ["mean", "byzantine_sgd"]:
        t = iters_to_eps_batch(res.stats[agg].gaps, eps)
        _emit_quantiles(f"table1/alpha0/{agg}", t)

    # --- α sweep under sign-flip: one campaign per α, so Krum's f and the
    # trim fraction are sized for that α (the nominal cfg.alpha configures
    # the baselines; only the seeds ride the grid axis here) ---
    for alpha in [0.125, 0.25, 0.375]:
        cfg_a = cfg._replace(alpha=alpha, attack="sign_flip")
        grid = expand_grid([("sign_flip", scenario_static("sign_flip"))],
                           [alpha], SEEDS)
        res = run_campaign(
            prob, cfg_a, grid,
            ["mean", "byzantine_sgd", "coordinate_median", "krum",
             "trimmed_mean"],
            return_gaps=True,
        )
        for agg in res.stats:
            t = iters_to_eps_batch(res.stats[agg].gaps, eps)
            _emit_quantiles(f"table1/alpha{alpha}/{agg}", t)

    # --- guard backends are Table-1-invariant (DESIGN.md §9): the dense,
    # fused-Pallas, and distributed-sketch realizations of the same filter
    # must land the same T-to-ε distribution (one campaign, backend axis;
    # sketch_dim=8 < d so the sketch rows carry real compression noise) ---
    cfg_b = cfg._replace(alpha=0.25, attack="sign_flip",
                         guard_opts=(("sketch_dim", 8),))
    grid = expand_grid([("sign_flip", scenario_static("sign_flip"))],
                       [0.25], SEEDS)
    res = run_campaign(prob, cfg_b, grid, ["byzantine_sgd"],
                       return_gaps=True,
                       backends=["dense", "fused", "dp_sketch"])
    for name in sorted(res.stats):
        t = iters_to_eps_batch(res.stats[name].gaps, eps)
        _emit_quantiles(f"table1/backend/{name.partition('@')[2]}", t)

    # --- parallel speedup in m (Remark 1.2); m is static → one jit per m ---
    for m in [4, 8, 16, 32]:
        cfg_m = SolverConfig(m=m, T=T, eta=0.05, alpha=0.25,
                             aggregator="byzantine_sgd", attack="sign_flip")
        grid = expand_grid([("sign_flip", scenario_static("sign_flip"))],
                           [0.25], SEEDS)
        res = run_campaign(prob, cfg_m, grid, ["byzantine_sgd"],
                           return_gaps=True)
        t = iters_to_eps_batch(res.stats["byzantine_sgd"].gaps, eps)
        _emit_quantiles(f"table1/speedup/m{m}", t)


if __name__ == "__main__":
    enable_compile_cache()
    main()
