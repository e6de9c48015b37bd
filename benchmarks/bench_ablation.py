"""Ablations on the framework's beyond-paper knobs.

1. sketch_dim — detection latency + final loss of the sketch-mode guard vs
   the exact mode, on a reduced LM under sign-flip. Quantifies the
   accuracy cost of the O(W·k) communication mode.
2. threshold slack — how much threshold inflation the filter tolerates
   before Byzantine leakage appears (robustness of the V auto-calibration).
3. threshold_mode — anytime (Lemma-3.6) vs fixed (Algorithm-1 header)
   thresholds: detection latency on the convex problem.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.solver import SolverConfig, run_sgd
from repro.data.problems import make_quadratic_problem
from repro.data.synthetic import SyntheticTokens, make_worker_batch
from repro.distributed.trainer import build_train_step, init_train_state, rank_from_mask
from repro.models import build_model
from repro.optim import adamw
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache


def sketch_dim_ablation() -> None:
    cfg = get_config("internlm2-1.8b").reduced(max_d_model=128)
    model = build_model(cfg)
    W, steps = 8, 25
    stream = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=32)
    opt = adamw(3e-3, grad_clip=1.0)
    rank = rank_from_mask(jnp.arange(W) < 2)
    for backend, k in [("dp_exact", 0), ("dp_sketch", 256),
                       ("dp_sketch", 1024), ("dp_sketch", 4096)]:
        scfg = SolverConfig(m=W, T=steps, eta=3e-3, alpha=0.25,
                            aggregator="byzantine_sgd", attack="sign_flip",
                            mean_over_alive=True, guard_backend=backend,
                            guard_opts=(("sketch_dim", max(k, 1)),))
        ts = jax.jit(build_train_step(model, opt, scfg))
        state = init_train_state(model, opt, scfg, jax.random.PRNGKey(0))
        detect = -1
        for i in range(steps):
            batch = make_worker_batch(stream, W, 2, jnp.asarray(i))
            state, m = ts(state, batch, rank, jax.random.PRNGKey(i))
            if detect < 0 and int(m["byz_alive"]) == 0:
                detect = i + 1
        emit(f"ablation/sketch_dim/{backend}{k}", float(detect),
             f"detect_step={detect},loss={float(m['loss_good_workers']):.4f},"
             f"good_filtered={int(m['good_filtered'])}")


def threshold_mode_ablation() -> None:
    prob = make_quadratic_problem(d=16, sigma=1.0, L=8.0, V=1.0, seed=0)
    for mode in ["anytime", "fixed"]:
        cfg = SolverConfig(m=16, T=2000, eta=0.05, alpha=0.25,
                           aggregator="byzantine_sgd", attack="alie",
                           threshold_mode=mode)
        res = run_sgd(prob, cfg, jax.random.PRNGKey(0))
        n_alive = np.asarray(res.n_alive)
        target = 16 - int(np.asarray(res.byz_mask).sum())
        det = np.where(n_alive <= target)[0]
        latency = int(det[0]) + 1 if det.size else -1
        gap = float(prob.f(res.x_avg) - prob.f(prob.x_star))
        emit(f"ablation/threshold_mode/{mode}", float(latency),
             f"detect_iter={latency},gap={gap:.5f},"
             f"good_filtered={bool(res.ever_filtered_good)}")


def main() -> None:
    sketch_dim_ablation()
    threshold_mode_ablation()


if __name__ == "__main__":
    enable_compile_cache()
    main()
