"""Claim 3.5 + §1.3 — filter behaviour: detection latency per attack class,
good-worker false-positive rate, and the hidden-shift damage bound.

Also benchmarks the guard *pipeline* itself: the dense three-pass reference
vs the fused one-pass Pallas path (DESIGN.md §5), at **both statistics
precisions** of the ``stats_dtype`` axis (§5 Numerics) — recording the
analytic bytes-moved model from :mod:`repro.roofline.guard_cost`, measured
wall-clock, dense/fused agreement per dtype, and the bf16-vs-f32 filter-
decision agreement into ``BENCH_filtering.json``.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import chip_peaks, emit, time_fn, write_json
from repro.core.byzantine_sgd import ByzantineGuard, GuardConfig
from repro.core.solver import SolverConfig, run_sgd
from repro.data.problems import make_generated_problem, make_quadratic_problem
from repro.kernels import gradgen, ops, ref
from repro.roofline.guard_cost import backend_cost, stats_elem_bytes
from repro.roofline.guard_cost import steady_state_us
from repro.launch.compile_cache import enable_compile_cache


def bench_detection_latency() -> None:
    prob = make_quadratic_problem(d=16, sigma=1.0, L=8.0, V=1.0, seed=0)
    for attack in ["sign_flip", "random_gaussian", "alie", "constant_drift",
                   "inner_product", "hidden_shift"]:
        cfg = SolverConfig(m=16, T=2000, eta=0.05, alpha=0.25,
                           aggregator="byzantine_sgd", attack=attack)
        res = run_sgd(prob, cfg, jax.random.PRNGKey(0))
        n_alive = np.asarray(res.n_alive)
        n_byz = int(np.asarray(res.byz_mask).sum())
        target = 16 - n_byz
        detected = np.where(n_alive <= target)[0]
        latency = int(detected[0]) + 1 if detected.size else -1
        gap = float(prob.f(res.x_avg) - prob.f(prob.x_star))
        emit(f"filter/{attack}", float(latency),
             f"detect_iter={latency},final_alive={int(n_alive[-1])},"
             f"good_filtered={bool(res.ever_filtered_good)},gap={gap:.5f}")


def bench_guard_pipeline(m: int = 32, d: int = 1 << 20, iters: int = 5,
                         d_block: int | None = None,
                         out_path: str = "BENCH_filtering.json") -> dict:
    """Dense vs fused guard step at the ISSUE's headline shape, at both
    statistics precisions (f32 and bf16 — ``SolverConfig.stats_dtype``).

    Bytes-moved comes from the roofline model (the quantity that predicts
    TPU wall-clock — the guard is memory-bound); wall-clock is measured on
    the current backend (on CPU the fused path runs the Pallas interpreter,
    so only the TPU-relevant bytes model is comparable across paths).

    ``d_block=None`` picks the kernel's VMEM-sized default (2048) on TPU;
    under the interpreter there is no VMEM budget, so a wide 2¹⁶ block
    keeps the grid short (interpreter time scales with grid steps).
    """
    if d_block is None:
        d_block = (1 << 16) if ops.interpret_mode() else 2048
    hw = chip_peaks()
    # V matched to the i.i.d.-normal worker data (‖g_i − g_j‖ ≈ √(2d)): the
    # filter keeps honest workers, so the recorded good_k / ξ agreement
    # compares *live* decisions rather than the everyone-filtered
    # degenerate state a V=1 guard collapses to at this d
    cfg = GuardConfig(m=m, T=1000, V=float(np.sqrt(2.0 * d)), D=10.0)

    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    grads = jax.random.normal(k1, (m, d), jnp.float32)
    x1 = jnp.zeros((d,), jnp.float32)
    xk = 0.01 * jax.random.normal(k2, (d,), jnp.float32)
    grads2 = jax.random.normal(k3, (m, d), jnp.float32)

    # in-kernel generation point (DESIGN.md §14): the same guard shape, but
    # rows regenerated from the counter-based PRNG inside the sweep instead
    # of read from HBM.  An ALIE coalition on the first quarter of the fleet
    # exercises the per-strip attack statistics (honest mean/std) in-kernel.
    from repro.core.attacks import alie_z_max

    gprob = make_generated_problem(d=d, sigma=1.0, L=8.0,
                                   V=float(np.sqrt(2.0 * d)), seed=0)
    wk1 = gradgen.key_bits(jax.random.split(jax.random.PRNGKey(5), m))
    wk2 = gradgen.key_bits(jax.random.split(jax.random.PRNGKey(6), m))
    gen_mask = jnp.arange(m) < m // 4
    gen_slot = jnp.where(gen_mask, 1, 0).astype(jnp.int32)
    tg = gradgen.mean_grad(gprob.gen.h, xk, gprob.gen.x_star)
    gen_params = (
        jnp.zeros((gradgen.GEN_NPARAMS,), jnp.float32)
        .at[gradgen.P_ID_A].set(4.0)  # ATTACK_TABLE id: alie
        .at[gradgen.P_Z_A].set(alie_z_max(m, jnp.sum(gen_mask)))
        .at[gradgen.P_TGNRM].set(jnp.maximum(jnp.linalg.norm(tg), 1e-12))
        .at[gradgen.P_NSCALE].set(gprob.gen.noise_scale)
    )
    zeros_m = jnp.zeros((m,), jnp.float32)

    def genctx(keys):
        return gradgen.GenStepCtx(worker_keys=keys, skewsign=zeros_m,
                                  slot=gen_slot, params=gen_params,
                                  w_byz=gen_mask.astype(jnp.float32))

    def gen_rows(keys):
        return jax.jit(ref.gen_rows_ref)(
            xk, gprob.gen.h, gprob.gen.x_star, gprob.gen.het_dir,
            keys, zeros_m, gen_slot, gen_params)

    per_dtype: dict[str, dict] = {}
    fused_alive: dict[str, jax.Array] = {}
    fused_xi: dict[str, jax.Array] = {}
    for sdt in ("f32", "bf16"):
        dense = ByzantineGuard(cfg, stats_dtype=sdt)
        fused = ByzantineGuard(cfg, use_fused=True, d_block=d_block,
                               stats_dtype=sdt)
        # one burn-in step so B ≠ 0 and the incremental Gram is exercised
        state_d = dense.step(dense.init(d), grads, xk, x1)[0]
        state_f = fused.step(fused.init(d), grads, xk, x1)[0]

        dense_step = jax.jit(dense.step)
        fused_step = jax.jit(fused.step)
        t_dense = time_fn(dense_step, state_d, grads2, xk, x1,
                          warmup=1, iters=iters)
        t_fused = time_fn(fused_step, state_f, grads2, xk, x1,
                          warmup=1, iters=iters)

        # agreement of the two paths on identical inputs (the oracle
        # contract, per stats dtype)
        sd, xi_d, _ = jax.block_until_ready(dense_step(state_d, grads2, xk, x1))
        sf, xi_f, _ = jax.block_until_ready(fused_step(state_f, grads2, xk, x1))
        fused_alive[sdt], fused_xi[sdt] = sf.alive, xi_f
        gb_err = float(jnp.linalg.norm(sf.gram_B - sd.gram_B)
                       / jnp.maximum(jnp.linalg.norm(sd.gram_B), 1e-12))
        xi_err = float(jnp.max(jnp.abs(xi_f - xi_d)))
        good_eq = bool(jnp.all(sf.alive == sd.alive))

        # gen point: identical row history delivered two ways — materialized
        # strips through the fused guard vs in-kernel regeneration through
        # gen_step (the differential oracle at the headline shape)
        geng = ByzantineGuard(cfg, use_fused=True, d_block=d_block,
                              stats_dtype=sdt, gen_spec=gprob.gen)
        gen_step = jax.jit(geng.gen_step)
        state_g = gen_step(geng.init(d), genctx(wk1), xk, x1)[0]
        t_gen = time_fn(gen_step, state_g, genctx(wk2), xk, x1,
                        warmup=1, iters=iters)
        sg, xi_g, _, _ = jax.block_until_ready(
            gen_step(state_g, genctx(wk2), xk, x1))
        state_fm = fused_step(fused.init(d), gen_rows(wk1), xk, x1)[0]
        sm, xi_m, _ = jax.block_until_ready(
            fused_step(state_fm, gen_rows(wk2), xk, x1))
        gen_agree = {
            "good_k_equal": bool(jnp.all(sg.alive == sm.alive)),
            "xi_max_abs_err": float(jnp.max(jnp.abs(xi_g - xi_m))),
            "n_alive": int(jnp.sum(sg.alive)),
        }

        cd = backend_cost("dense", m, d, sdt)
        cf = backend_cost("fused", m, d, sdt)
        cg = backend_cost("gen", m, d, sdt)
        per_dtype[sdt] = {
            "elem_bytes": stats_elem_bytes(sdt),
            # analytic HBM-traffic model (repro.roofline.guard_cost), NOT
            # a measurement — the ratios follow from counting the passes
            # each path makes over (m, d) data; wallclock_us below is what
            # was actually measured on this backend
            "bytes_moved_model": {
                "source": "repro.roofline.guard_cost",
                "dense": {"stats": cd.stats_bytes, "xi": cd.xi_bytes,
                          "step": cd.step_bytes},
                "fused": {"stats": cf.stats_bytes, "xi": cf.xi_bytes,
                          "step": cf.step_bytes},
                "gen": {"stats": cg.stats_bytes, "xi": cg.xi_bytes,
                        "step": cg.step_bytes},
                "stats_ratio": cd.stats_bytes / cf.stats_bytes,
                "step_ratio": cd.step_bytes / cf.step_bytes,
                "gen_step_ratio": cf.step_bytes / cg.step_bytes,
            },
            "wallclock_us": {"dense": t_dense, "fused": t_fused,
                             "gen": t_gen},
            # measured / bandwidth-modeled ratio of the gen step on the
            # chip it ran on — the measured-vs-modeled band; None off the
            # TPU, where there is no chip to model
            "gen_measured_over_model": (
                t_gen / max(steady_state_us(cg, hw), 1e-12)
                if hw is not None else None),
            "agreement": {"gram_B_rel_err": gb_err,
                          "xi_max_abs_err": xi_err,
                          "good_k_equal": good_eq,
                          # visible guard against the all-filtered
                          # degenerate state (where agreement is vacuous)
                          "n_alive": int(jnp.sum(sf.alive))},
            "gen_vs_fused": gen_agree,
        }
        emit(f"filter/guard_step_dense_{sdt}", t_dense,
             f"model_stats_bytes={cd.stats_bytes}")
        emit(f"filter/guard_step_fused_{sdt}", t_fused,
             f"model_stats_bytes={cf.stats_bytes},"
             f"model_stats_ratio={cd.stats_bytes / cf.stats_bytes:.2f},"
             f"model_step_ratio={cd.step_bytes / cf.step_bytes:.2f},"
             f"interpret={ops.interpret_mode()}")
        emit(f"filter/guard_step_gen_{sdt}", t_gen,
             f"model_step_bytes={cg.step_bytes},"
             f"model_gen_step_ratio={cf.step_bytes / cg.step_bytes:.2f},"
             f"good_k_equal={gen_agree['good_k_equal']},"
             f"xi_err={gen_agree['xi_max_abs_err']:.2e},"
             f"interpret={ops.interpret_mode()}")

    # the dtype axis headline (ISSUE 5): fused@bf16 must model ≤ 0.55× the
    # fused@f32 statistics bytes, and the saved bytes must not change the
    # filter's decisions on this step
    f32_stats = per_dtype["f32"]["bytes_moved_model"]["fused"]["stats"]
    bf16_stats = per_dtype["bf16"]["bytes_moved_model"]["fused"]["stats"]
    xi_rel = float(
        jnp.linalg.norm(fused_xi["bf16"].astype(jnp.float32) - fused_xi["f32"])
        / jnp.maximum(jnp.linalg.norm(fused_xi["f32"]), 1e-12)
    )
    bf16_vs_f32 = {
        "fused_stats_bytes_ratio_model": bf16_stats / f32_stats,
        "good_k_equal": bool(jnp.all(fused_alive["bf16"] == fused_alive["f32"])),
        "xi_rel_err": xi_rel,
    }
    record = {
        "m": m,
        "d": d,
        "d_block": d_block,
        "backend": jax.default_backend(),
        "fused_runs_interpret": ops.interpret_mode(),
        "stats_dtypes": per_dtype,
        "bf16_vs_f32": bf16_vs_f32,
    }
    write_json(out_path, record)
    emit("filter/stats_dtype_bf16_ratio",
         bf16_vs_f32["fused_stats_bytes_ratio_model"],
         f"good_k_equal={bf16_vs_f32['good_k_equal']},"
         f"xi_rel_err={xi_rel:.2e},out={out_path}")
    return record


def main(m: int = 32, d: int = 1 << 20, iters: int = 5,
         d_block: int | None = None,
         out_path: str = "BENCH_filtering.json",
         pipeline_only: bool = False) -> None:
    if not pipeline_only:
        bench_detection_latency()
    bench_guard_pipeline(m=m, d=d, iters=iters, d_block=d_block,
                         out_path=out_path)


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--m", type=int, default=32)
    ap.add_argument("--d", type=int, default=1 << 20)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--d-block", type=int, default=None,
                    help="fused-kernel strip width (default: 2048 on TPU, "
                         "2^16 under the interpreter)")
    ap.add_argument("--out", default="BENCH_filtering.json")
    ap.add_argument("--pipeline-only", action="store_true",
                    help="skip the detection-latency sweep")
    args = ap.parse_args()
    if args.d_block is not None and args.d_block <= 0:
        ap.error("--d-block must be a positive strip width")
    main(m=args.m, d=args.d, iters=args.iters, d_block=args.d_block,
         out_path=args.out, pipeline_only=args.pipeline_only)
