"""Training driver.

Runs real steps on whatever devices exist (CPU harness: reduced configs;
TPU pod: full configs — identical code path).  Byzantine workers are
simulated on the worker axis; the guard backend, optimizer, data pipeline
and checkpointing are all exercised.

Aggregation is the solver's guard axis (DESIGN.md §9/§10):
``--aggregator byzantine_sgd`` with ``--guard-backend`` one of

* ``dp_exact``  — the distributed exact-mode guard (auto-V online; default)
* ``dp_sketch`` — the CountSketch guard (O(W·k) statistics)
* ``dense`` / ``fused`` — the single-host reference / one-pass Pallas
  pipeline; no auto-V, so pass ``--guard-v`` (the Assumption-2.2 bound)

or any stateless baseline (``mean`` / ``coordinate_median`` /
``trimmed_mean`` / ``krum``) via ``--aggregator``.

The adversary is either a static gradient attack (``--attack``) or a full
Remark-2.3 *scenario* (``--scenario``) built around that attack:

* ``static``    — the plain attack (same as no scenario, via the engine)
* ``lie_low``   — honest until T/2, then strike
* ``churn``     — Byzantine identity rotates every T/2 steps
* ``adaptive``  — multiplicative-weights magnitude driven by filter feedback
* ``coalition`` — half the coalition plays the attack, half inner_product

The step loop is a **chunked ``lax.scan``**: data generation, the attack,
the guard and the optimizer all live inside one jitted scan over
``log_every`` steps, so the host sees one transfer of stacked metrics per
chunk instead of one transfer per metric per step (the historical Python
loop is kept as ``driver="loop"`` — it is the measured baseline in
``BENCH_train.json``, see ``benchmarks/bench_train.py``).

Checkpointing stores the **full** :class:`~repro.distributed.trainer.TrainState`
(params + optimizer moments + guard martingales + anchor + adversary and
feedback memory + step), so ``--resume`` continues bit-for-bit where the
run stopped (resume-equals-uninterrupted is a tier-1 regression test).

PRNG discipline: one ``jax.random.split`` at the top fans the seed into
disjoint init / mask / data / loop streams — the init key can no longer
collide with the Byzantine-mask permutation, and the per-step data and
attack keys live in separate streams.

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --workers 8 --steps 100 --alpha 0.25 --attack sign_flip \
        --guard-backend dp_exact --scenario churn
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import signal
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.core.solver import SolverConfig, byz_rank
from repro.data.synthetic import SyntheticTokens, make_worker_batch
from repro.distributed.trainer import build_train_step, init_train_state
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.obs import EventLog, TelemetryConfig, trace_span
from repro.optim import adamw, linear_warmup_cosine

GUARD_BACKENDS = ("dp_exact", "dp_sketch", "dense", "fused")
SCENARIOS = ("static", "lie_low", "churn", "adaptive", "coalition")


def _make_scenario_adversary(name: str, attack: str, alpha: float,
                             steps: int, workers: int):
    from repro.scenarios import (
        ScenarioAdversary,
        scenario_adaptive,
        scenario_churn,
        scenario_coalition,
        scenario_lie_low_then_strike,
        scenario_static,
    )

    if name == "static":
        scn = scenario_static(attack)
    elif name == "lie_low":
        scn = scenario_lie_low_then_strike(attack, switch_step=steps // 2)
    elif name == "churn":
        scn = scenario_churn(attack, period=max(steps // 2, 1),
                             stride=max(workers // 8, 1))
    elif name == "adaptive":
        scn = scenario_adaptive(attack, adapt_rate=0.5)
    elif name == "coalition":
        scn = scenario_coalition(attack, "inner_product", 0.5)
    else:
        raise KeyError(f"unknown scenario {name!r}; have {SCENARIOS}")
    return ScenarioAdversary(scenario=scn, alpha=jnp.float32(alpha))


def run_training(
    arch: str, *, reduced: bool = True, workers: int = 8, per_worker_batch: int = 2,
    seq_len: int = 128, steps: int = 100, alpha: float = 0.25,
    attack: str = "sign_flip", aggregator: str = "byzantine_sgd",
    guard_backend: str = "dp_exact", guard_opts: tuple = (),
    stats_dtype: str = "f32",
    guard_v: float = 0.0, scenario: str | None = None, lr: float = 3e-3,
    seed: int = 0, ckpt_dir: str | None = None, resume: bool = False,
    stop_after: int | None = None, log_every: int = 10, d_model: int = 256,
    driver: str = "scan", trace: str | None = None,
    ckpt_every: int | None = None, keep_last: int | None = None,
):
    """Train ``steps`` steps; returns (final TrainState, per-step history).

    ``trace`` (a path) arms the guard flight recorder (DESIGN.md §12):
    per-step filter forensics ride the chunk flush as ``tel/`` metrics and
    are written — together with ``train/chunk`` host spans and the run's
    provenance — as structured JSONL at that path
    (``scripts/render_trace.py`` renders it; ``--perfetto`` converts).

    ``driver="scan"`` (default) runs chunked ``lax.scan`` with on-device
    data generation; ``driver="loop"`` is the historical one-jitted-call-
    per-step Python loop with per-metric host transfers, retained only as
    the wall-clock baseline.

    ``stop_after`` interrupts the run after that many steps while keeping
    every schedule (LR, thresholds, scenario switch points) sized by the
    full ``steps`` — with ``ckpt_dir`` set this checkpoints a resumable
    prefix, which is how the resume-equals-uninterrupted regression test
    simulates a preempted run.

    ``ckpt_every`` (with ``ckpt_dir``) also checkpoints mid-run every that
    many steps at segment boundaries — the periodic saves a SIGKILL-style
    crash resumes from (the chaos harness's kill-resume matrix);
    ``keep_last`` bounds retention to the newest K complete checkpoints.
    A SIGTERM (preemption notice) is caught at the next segment boundary:
    the loop exits early and the normal tail flushes a final checkpoint +
    history within the grace budget (DESIGN.md §15).
    """
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced(max_d_model=d_model)
    model = build_model(cfg)
    stream = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=seq_len, seed=seed)
    opt = adamw(linear_warmup_cosine(lr, warmup=max(steps // 20, 1), total_steps=steps),
                grad_clip=1.0)
    # label_flip poisons the DATA of Byzantine workers (their gradients are
    # honest gradients of corrupted batches) — no gradient-level transform
    grad_attack = "none" if attack == "label_flip" else attack
    if scenario is not None and attack == "label_flip":
        raise ValueError("label_flip is a data attack; scenarios schedule "
                         "gradient attacks — pick one")
    scfg = SolverConfig(
        m=workers, T=steps, eta=lr, alpha=alpha, aggregator=aggregator,
        attack=grad_attack, mean_over_alive=True,
        guard_backend=guard_backend, guard_opts=tuple(guard_opts),
        stats_dtype=stats_dtype,
    )
    adversary = (_make_scenario_adversary(scenario, grad_attack, alpha,
                                          steps, workers)
                 if scenario is not None else None)
    telemetry = TelemetryConfig(enabled=True) if trace else None
    elog = None
    if trace:
        elog = EventLog(
            tool="repro.launch.train", arch=arch, workers=workers,
            steps=steps, alpha=alpha, attack=attack, aggregator=aggregator,
            guard_backend=guard_backend, scenario=scenario, seed=seed,
        )
    train_step = build_train_step(model, opt, scfg, V=guard_v,
                                  adversary=adversary, telemetry=telemetry)

    # PRNG: one split at the top → disjoint init / mask / data / loop streams
    init_key, mask_key, data_key, loop_key = jax.random.split(
        jax.random.PRNGKey(seed), 4
    )
    state = init_train_state(model, opt, scfg, init_key, V=guard_v,
                             adversary=adversary)
    rank = byz_rank(mask_key, workers)
    static_mask = rank < scfg.n_byzantine
    poison = static_mask if attack == "label_flip" else None

    start = 0
    history: list[dict] = []
    if resume and ckpt_dir and latest_step(ckpt_dir) is not None:
        state, start = restore_checkpoint(ckpt_dir, state)
        print(f"resumed from {ckpt_dir} at step {start}")
        hist_path = os.path.join(ckpt_dir, "history.json")
        if os.path.exists(hist_path):
            # keep the pre-resume records so history.json stays complete
            with open(hist_path) as f:
                history = [r for r in json.load(f) if r["step"] < start]
    stop = steps if stop_after is None else min(stop_after, steps)

    def make_batch(i):
        batch = make_worker_batch(stream, workers, per_worker_batch, i,
                                  poison_mask=poison)
        if cfg.frontend != "none":
            fseq = cfg.frontend_seq if not cfg.enc_dec else cfg.enc_seq_len
            batch["frontend"] = 0.02 * jax.random.normal(
                jax.random.fold_in(data_key, i),
                (workers, per_worker_batch, fseq, cfg.frontend_dim),
                jnp.dtype(cfg.activation_dtype),
            )
        return batch

    def one_step(st, i):
        batch = make_batch(i)
        return train_step(st, batch, rank, jax.random.fold_in(loop_key, i))

    t0 = time.time()
    n_prior = len(history)
    run_label = f"train/{arch}"

    # preemption (DESIGN.md §15): SIGTERM flips a flag the drivers check at
    # segment boundaries — the loop exits early and the normal tail below
    # flushes a final checkpoint + history within the grace budget, instead
    # of dying mid-scan with the newest progress only in device memory.
    preempted = {"hit": False}
    prev_sigterm = None
    if ckpt_dir:
        def _on_sigterm(signum, frame):
            preempted["hit"] = True
        try:
            prev_sigterm = signal.signal(signal.SIGTERM, _on_sigterm)
        except ValueError:
            prev_sigterm = None  # not the main thread — no handler, no flush

    def maybe_ckpt(state, lo):
        """Periodic mid-run save at a segment boundary (the restart points
        of the kill-resume chaos matrix)."""
        if ckpt_dir and ckpt_every and lo < stop and lo % ckpt_every == 0:
            save_checkpoint(ckpt_dir, int(jax.device_get(state.step)), state,
                            keep_last=keep_last)

    def flush_recs(ms, lo, hi, seg_s, stacked=True):
        """Host-side split of one metrics transfer: ``tel/`` forensics
        (per-worker arrays included) go to the event log as guard_step
        events, everything else becomes scalar history records.
        ``step_s`` is the host wall time of the segment the step ran in
        (dispatch to metrics on the host, compilation included on the first
        call of each program) over its step count."""
        for j, i in enumerate(range(lo, hi)):
            rec, frame = {}, {}
            for k, v in ms.items():
                vj = v[j] if stacked else v
                if k.startswith("tel/"):
                    frame[k[4:]] = vj
                else:
                    rec[k] = float(vj)
            rec["step"] = i
            rec["step_s"] = seg_s / (hi - lo)
            history.append(rec)
            if elog is not None and frame:
                elog.guard_step(frame, run=run_label)

    def log(rec):
        print(
            f"step {rec['step']:5d}  loss={rec['loss_good_workers']:.4f}  "
            f"alive={int(rec['n_alive'])}/{workers}  "
            f"byz_alive={int(rec.get('byz_alive', 0))}  "
            f"good_filtered={int(rec.get('good_filtered', 0))}  "
            f"({(time.time()-t0)/max(len(history) - n_prior, 1):.2f}s/step)"
        )

    if driver == "scan":
        # fixed compile set regardless of steps/stop/resume offsets: full
        # log_every chunks go through ONE scan program; ragged head/tail
        # segments (resume from an unaligned step, final remainder) run
        # through the shared per-step program instead of retracing the
        # whole model scan at a new length.  The carried state is donated:
        # the loop only rebinds `state` to the result, and without donation
        # the old and new TrainState are live at once (a full-width
        # mamba2-130m run at W=8 then no longer fits a 16 GiB chip)
        @functools.partial(jax.jit, donate_argnums=0)
        def run_chunk(st, idx):
            def body(s, i):
                s, m = one_step(s, i)
                return s, m
            return jax.lax.scan(body, st, idx)

        step_fn = jax.jit(one_step, donate_argnums=0)

        def run_segment(state, lo, hi):
            if hi - lo == log_every:
                t_seg = time.perf_counter()
                with trace_span("train/chunk", log=elog, lo=lo, hi=hi):
                    state, ms = run_chunk(state, jnp.arange(lo, hi))
                    ms = jax.device_get(ms)
                flush_recs(ms, lo, hi, time.perf_counter() - t_seg)
            else:
                for i in range(lo, hi):
                    t_seg = time.perf_counter()
                    with trace_span("train/step", log=elog, i=i):
                        state, m = step_fn(state, jnp.asarray(i))
                        m = jax.device_get(m)
                    flush_recs(m, i, i + 1, time.perf_counter() - t_seg,
                               stacked=False)
            return state

        lo = start
        head = max(min((log_every - start % log_every) % log_every,
                       stop - start), 0)
        if head:
            state = run_segment(state, lo, lo + head)
            log(history[-1])
            lo += head
            maybe_ckpt(state, lo)
        while lo < stop and not preempted["hit"]:
            hi = min(lo + log_every, stop)
            state = run_segment(state, lo, hi)
            log(history[-1])
            lo = hi
            maybe_ckpt(state, lo)
    elif driver == "loop":
        # historical baseline: one jitted call + one host transfer per
        # metric per step (what the scan driver replaces)
        step_fn = jax.jit(one_step)
        for i in range(start, stop):
            if preempted["hit"]:
                break
            t_seg = time.perf_counter()
            state, metrics = step_fn(state, jnp.asarray(i))
            flush_recs(jax.device_get(metrics), i, i + 1,
                       time.perf_counter() - t_seg, stacked=False)
            if i % log_every == 0 or i == stop - 1:
                log(history[-1])
            maybe_ckpt(state, i + 1)
    else:
        raise KeyError(f"unknown driver {driver!r}; have scan|loop")

    if preempted["hit"]:
        print(f"SIGTERM: preempted at step {int(jax.device_get(state.step))}"
              " — flushing final checkpoint")
    if ckpt_dir:
        # label with the state's own counter — when a resume starts at or
        # past `stop` no steps ran and the label must not go backwards
        save_checkpoint(ckpt_dir, int(jax.device_get(state.step)), state,
                        keep_last=keep_last)
        with open(f"{ckpt_dir}/history.json", "w") as f:
            json.dump(history, f)
    if prev_sigterm is not None:
        signal.signal(signal.SIGTERM, prev_sigterm)
    if elog is not None:
        elog.add_meta(wall_s=time.time() - t0,
                      steps_run=max(stop - start, 0))
        elog.write_jsonl(trace)
        print(f"wrote trace {trace} ({len(elog.events)} events)")
    return state, history


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--per-worker-batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--alpha", type=float, default=0.25)
    ap.add_argument("--attack", default="sign_flip",
                    choices=["none", "sign_flip", "random_gaussian",
                             "constant_drift", "alie", "inner_product",
                             "hidden_shift", "label_flip"])
    ap.add_argument("--aggregator", default="byzantine_sgd",
                    choices=["byzantine_sgd", "mean", "coordinate_median",
                             "trimmed_mean", "krum"])
    ap.add_argument("--guard-backend", default="dp_exact",
                    choices=list(GUARD_BACKENDS),
                    help="guard realization (DESIGN.md §9); dense/fused "
                         "need --guard-v")
    ap.add_argument("--stats-dtype", default="f32", choices=["f32", "bf16"],
                    help="guard statistics precision (DESIGN.md §5 "
                         "Numerics): bf16 halves the filter pipeline's "
                         "HBM traffic; gradients cast once at ravel")
    ap.add_argument("--guard-v", type=float, default=0.0,
                    help="explicit Assumption-2.2 V (0 = auto-calibrate, "
                         "dp backends only)")
    ap.add_argument("--scenario", default=None, choices=list(SCENARIOS),
                    help="Remark-2.3 scenario adversary built around "
                         "--attack (default: static attack path)")
    ap.add_argument("--driver", default="scan", choices=["scan", "loop"])
    ap.add_argument("--d-model", type=int, default=256,
                    help="reduced-config width cap (CPU harness sizing)")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=None, metavar="N",
                    help="also checkpoint every N steps mid-run (at segment "
                         "boundaries) — the restart points a SIGKILL-style "
                         "crash resumes from")
    ap.add_argument("--keep-last", type=int, default=None, metavar="K",
                    help="retain only the newest K complete checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in --ckpt-dir")
    ap.add_argument("--stop-after", type=int, default=None, metavar="N",
                    help="stop after N steps (schedules stay sized by "
                         "--steps) — checkpoints a resumable prefix")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="arm the guard flight recorder (DESIGN.md §12) and "
                         "write the structured JSONL event log here; render "
                         "with scripts/render_trace.py")
    args = ap.parse_args()
    enable_compile_cache()
    run_training(
        args.arch, reduced=args.reduced, workers=args.workers,
        per_worker_batch=args.per_worker_batch, seq_len=args.seq_len,
        steps=args.steps, alpha=args.alpha, attack=args.attack,
        aggregator=args.aggregator, guard_backend=args.guard_backend,
        stats_dtype=args.stats_dtype,
        guard_v=args.guard_v, scenario=args.scenario, driver=args.driver,
        lr=args.lr, seed=args.seed, ckpt_dir=args.ckpt_dir,
        resume=args.resume, log_every=args.log_every, trace=args.trace,
        ckpt_every=args.ckpt_every, keep_last=args.keep_last,
        stop_after=args.stop_after, d_model=args.d_model,
    )


if __name__ == "__main__":
    main()
