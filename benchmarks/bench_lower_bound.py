"""Theorems 5.4/5.5 — the lower bound, made observable: success probability
of the distinguishing reduction vs T, sweeping through the α²V²D²/ε²
threshold. Below ⇒ coin-flip; above ⇒ certainty."""
from __future__ import annotations

import jax

from benchmarks.common import emit
from repro.core.lower_bound import (
    distinguishing_experiment_linear,
    distinguishing_experiment_strongly_convex,
)
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    key = jax.random.PRNGKey(0)
    alpha, eps = 0.3, 0.05
    for T in [2, 8, 32, 128, 512, 2048]:
        r = distinguishing_experiment_linear(
            key, m=16, T=T, n_trials=64, alpha=alpha, eps=eps)
        emit(f"lower_bound/linear/T{T}", float(T),
             f"success={float(r.success_rate):.3f},threshold_T={r.threshold_T:.0f}")
    for T in [2, 8, 32, 128, 512, 2048]:
        r = distinguishing_experiment_strongly_convex(
            key, m=16, T=T, n_trials=64, alpha=alpha, eps_hat=eps)
        emit(f"lower_bound/strongly_convex/T{T}", float(T),
             f"success={float(r.success_rate):.3f},threshold_T={r.threshold_T:.0f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
