#!/usr/bin/env python3
"""Smoke run of the Byzantine-robust trainer on TPU chips.

    python chip_smoke.py             # one chip: guard kernels, then trainer
    python chip_smoke.py --chips 4   # four chips: the worker-sharded step

Everything runs in this one process, which holds the chip(s); the code
under test never falls back to the CPU, to the Pallas interpreter or to
a jnp oracle.

One chip:

* guard kernels at m=32, d=2^20 (``ops.fused_guard`` f32 and bf16,
  ``ops.filtered_mean``, ``ops.fused_guard_gen``), run on the chip,
  against their ``ref`` oracles evaluated on the host CPU under
  ``default_matmul_precision("highest")``, with the tolerances of the
  kernel tests;
* the trainer through ``repro.launch.train.run_training`` at mamba2-130m's
  published widths (``reduced=False``): W=8 workers, seq 2048, α=0.25
  sign_flip, bf16 guard statistics, once with the default ``dp_exact``
  guard and once with the ``fused`` (Pallas) guard at the V that
  ``dp_exact`` estimated.  Loss must stay finite, no Byzantine worker may
  survive the last chunk and no honest worker may ever be filtered.

Four chips (``--chips 4``): W=4 workers on a (data=4, model=1) mesh, state
and batch placed by ``make_train_specs`` under the production logical
rules, a few ``dp_exact`` steps compared per step with the same step
unsharded on one device: identical alive masks, loss within
``LOSS_RTOL``.  The sharded program must hold collectives and span the
four devices.

Earlier lines report each phase; the last line of stdout is one JSON
object ``{"ok": true, "device": {...}}`` and is printed only when every
phase passed.  The script exits non-zero, with no such line, when JAX
finds no TPU or when the repository's ``src/`` is not next to it.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
import traceback
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

ARCH = "mamba2-130m"
SEQ_LEN = 2048          # a multiple of mamba2-130m's ssm_chunk (256)
LOG_EVERY = 4
CHUNKS = 3              # steps = CHUNKS * LOG_EVERY: scan program only
# the kernel tests' tolerances (tests/test_fused_guard.py, test_kernels.py)
F32_TOL, BF16_TOL = 1e-5, 1e-2
# one-device vs sharded loss of the same W=4 step: bf16 params and
# activations, different partial-sum order — a few bf16 ulps (2^-8)
LOSS_RTOL = 2e-2


class Smoke:
    """Collects phase failures and compile seconds for the whole run."""

    def __init__(self, jax):
        self.jax = jax
        self.failures: list[str] = []
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        # tracing, lowering and XLA compilation (a persistent-cache hit
        # shows up as a short backend compile)
        if event.startswith("/jax/core/compile/"):
            self.compile_s += duration

    def run(self, name: str, fn, *args, **kwargs):
        """Run one phase; an exception fails the phase, and the run goes
        on to the next so that one chip call reports every phase."""
        try:
            return fn(self, *args, **kwargs)
        except Exception:  # noqa: BLE001 — reported, and fails the run
            traceback.print_exc()
            self.check(False, f"{name} raised")
            return None

    def check(self, ok: bool, what: str) -> bool:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failures.append(what)
        return ok

    def peak_bytes(self, device) -> int:
        stats = device.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            raise RuntimeError(f"{device} reports no peak_bytes_in_use")
        return int(stats["peak_bytes_in_use"])


def _rel(got, want) -> tuple[float, float]:
    """(‖got − want‖, ‖want‖) in f64 on the host."""
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    return float(np.linalg.norm(g - w)), float(np.linalg.norm(w))


def _kernel_case(smoke: Smoke, name: str, fn, args, oracle, tol: float,
                 atol: float | None = None) -> None:
    """Compile ``fn`` on the chip, check it holds a Mosaic kernel, time a
    few calls and compare every output with ``oracle``: elementwise as
    ``assert_allclose(rtol=tol, atol=atol)`` when ``atol`` is given, else
    in norm, ‖got − want‖ ≤ tol·‖want‖ + tol.

    The oracle runs on the host CPU: XLA's f32 contractions on the TPU,
    even at "highest" precision, miss the d = 2^20 Grams by 2.4e-5 —
    more than the f32 tolerance the kernel is held to."""
    jax = smoke.jax
    c0 = smoke.compile_s
    text = jax.jit(fn).lower(*args).as_text()
    got = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    host = jax.device_put(args, jax.devices("cpu")[0])
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(oracle)(*host))
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    errs, ok = [], True
    for a, b in zip(got, want):
        if atol is not None:
            a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
            ok &= bool(np.all(np.abs(a64 - b64) <= atol + tol * np.abs(b64)))
            errs.append(float(np.max(np.abs(a64 - b64) / (np.abs(b64) + atol))))
        else:
            err, ref_n = _rel(a, b)
            ok &= err <= tol * ref_n + tol
            errs.append(err / max(ref_n, 1e-30))
    kind = "elementwise |err|/(|ref|+atol)" if atol is not None else "‖err‖/‖ref‖"
    print(f"  {name}: compile_s={smoke.compile_s - c0:.2f} "
          f"call_ms={[round(t * 1e3, 3) for t in times]} "
          f"rel_err per output={[f'{e:.3e}' for e in errs]} "
          f"({kind}, tol {tol:g})", flush=True)
    smoke.check("tpu_custom_call" in text, f"{name} holds a Mosaic kernel")
    smoke.check(ok, f"{name} matches its oracle within {tol:g}")


def guard_phase(smoke: Smoke, m: int = 32, d: int = 1 << 20) -> None:
    """The guard's kernels at the guard cell's shape against ``ref``."""
    jax = smoke.jax
    import jax.numpy as jnp

    from repro.core.attacks import alie_z_max
    from repro.data.problems import make_generated_problem
    from repro.kernels import gradgen, ops, ref

    print(f"phase guard_kernels m={m} d={d}", flush=True)
    # the inputs of a guard step at k = 10: the rows share a true gradient,
    # B_{k-1} has summed nine of them, and the iterate has moved against
    # it (δ = x_k − x_1).  Independent noise in all three would make each
    # output a cancelling sum of 2^20 terms, whose f32 rounding in the
    # host oracle alone comes within 1.5x of the f32 tolerance
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    true_g = jax.random.normal(keys[4], (d,), jnp.float32)
    g32 = true_g + jax.random.normal(keys[0], (m, d), jnp.float32)
    b32 = 9.0 * true_g + 3.0 * jax.random.normal(keys[1], (m, d), jnp.float32)
    dl32 = -0.1 * true_g + 0.01 * jax.random.normal(keys[2], (d,), jnp.float32)
    mask = jax.random.bernoulli(keys[3], 0.6, (m,))
    for dt, tol in ((jnp.float32, F32_TOL), (jnp.bfloat16, BF16_TOL)):
        tag = jnp.dtype(dt).name
        g, b, dl = g32.astype(dt), b32.astype(dt), dl32.astype(dt)
        _kernel_case(smoke, f"fused_guard[{tag}]",
                     lambda g, b, dl: ops.fused_guard(g, b, dl),
                     (g, b, dl), ref.fused_guard_ref, tol)
        _kernel_case(smoke, f"filtered_mean[{tag}]",
                     lambda x, w: ops.filtered_mean(x, w, float(m),
                                                    d_block=2048),
                     (g, mask), lambda x, w: ref.filtered_mean_ref(
                         x, w, float(m)),
                     *((F32_TOL, F32_TOL) if dt == jnp.float32
                       else (2e-2, 1e-2)))

    prob = make_generated_problem(d=d, sigma=1.0, L=8.0, V=1.0, seed=0)
    gen = prob.gen
    wkeys = gradgen.key_bits(jax.random.split(jax.random.PRNGKey(7), m))
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (d,), jnp.float32)
    byz = jnp.arange(m) < m // 4                  # an ALIE coalition
    slot = jnp.where(byz, 1, 0).astype(jnp.int32)
    tg = gradgen.mean_grad(gen.h, x, gen.x_star)
    params = (jnp.zeros((gradgen.GEN_NPARAMS,), jnp.float32)
              .at[gradgen.P_ID_A].set(4.0)
              .at[gradgen.P_Z_A].set(alie_z_max(m, jnp.sum(byz)))
              .at[gradgen.P_TGNRM].set(jnp.maximum(jnp.linalg.norm(tg), 1e-12))
              .at[gradgen.P_NSCALE].set(gen.noise_scale))
    skew = jnp.zeros((m,), jnp.float32)
    gen_args = (x, gen.h, gen.x_star, gen.het_dir, wkeys, skew, slot, params)
    b_gen = 9.0 * tg + 3.0 * jax.random.normal(keys[1], (m, d), jnp.float32)
    _kernel_case(smoke, "fused_guard_gen[float32]",
                 lambda b, dl, *a: ops.fused_guard_gen(b, dl, *a),
                 (b_gen, -0.1 * tg, *gen_args), ref.fused_guard_gen_ref,
                 F32_TOL)
    print(f"  peak_bytes_in_use={smoke.peak_bytes(jax.devices()[0])}",
          flush=True)


def _has_kernel(dump_dir: Path, module: str) -> bool:
    """True when the StableHLO that JAX handed the compiler for ``module``
    calls a Mosaic kernel."""
    files = sorted(dump_dir.glob(f"*{module}*_compile.mlir"))
    return bool(files) and all("tpu_custom_call" in f.read_text()
                               for f in files)


def trainer_phase(smoke: Smoke, guard: str, guard_v: float = 0.0, *,
                  reduced: bool = False, workers: int = 8,
                  seq_len: int = SEQ_LEN, log_every: int = LOG_EVERY,
                  chunks: int = CHUNKS) -> float:
    """``run_training`` through the scan driver; returns the last v_est."""
    jax = smoke.jax
    from repro.launch.train import run_training

    steps = chunks * log_every
    print(f"phase trainer guard={guard} arch={ARCH} reduced={reduced} "
          f"W={workers} seq={seq_len} steps={steps} guard_v={guard_v}",
          flush=True)
    c0 = smoke.compile_s
    prev_dump = jax.config.read("jax_dump_ir_to")
    with tempfile.TemporaryDirectory() as dump:
        jax.config.update("jax_dump_ir_to", dump)
        try:
            _, hist = run_training(
                ARCH, reduced=reduced, workers=workers, per_worker_batch=1,
                seq_len=seq_len, steps=steps, alpha=0.25,
                attack="sign_flip", guard_backend=guard, guard_v=guard_v,
                stats_dtype="bf16", log_every=log_every, seed=0)
        finally:
            jax.config.update("jax_dump_ir_to", prev_dump)
        kernel = _has_kernel(Path(dump), "jit_run_chunk")
    print(f"  compile_s={smoke.compile_s - c0:.2f} "
          f"step_s(per chunk)={[round(h['step_s'], 4) for h in hist[::log_every]]} "
          f"peak_bytes_in_use={smoke.peak_bytes(jax.devices()[0])} "
          f"tpu_custom_call={kernel}", flush=True)
    for h in hist:
        print(f"  step {h['step']:3d} loss={h['loss_good_workers']:.4f} "
              f"n_alive={int(h['n_alive'])} byz_alive={int(h['byz_alive'])} "
              f"good_filtered={int(h['good_filtered'])} v_est={h['v_est']:.4g}",
              flush=True)
    last = hist[-1]
    smoke.check(len(hist) == steps, f"{guard}: {steps} steps ran")
    smoke.check(all(math.isfinite(h["loss_good_workers"]) for h in hist),
                f"{guard}: loss finite")
    smoke.check(int(last["byz_alive"]) == 0, f"{guard}: byz_alive 0 at the end")
    smoke.check(all(int(h["good_filtered"]) == 0 for h in hist),
                f"{guard}: no honest worker filtered")
    if guard == "fused":
        smoke.check(kernel, "fused: the scan program holds tpu_custom_call")
    return float(last["v_est"])


def sharded_phase(smoke: Smoke, devices, *, reduced: bool = False,
                  workers: int = 4, seq_len: int = SEQ_LEN,
                  steps: int = 3) -> None:
    """The W-worker dp_exact step with the worker axis over ``devices``
    (placed as ``repro.launch.dryrun`` places it), against the same step
    unsharded on ``devices[0]``."""
    jax = smoke.jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.core.solver import SolverConfig, byz_rank
    from repro.data.synthetic import SyntheticTokens, make_worker_batch
    from repro.distributed.sharding import use_logical_rules
    from repro.distributed.specs import make_train_specs, rules_for
    from repro.distributed.trainer import build_train_step, init_train_state
    from repro.models import build_model
    from repro.optim import adamw

    n = len(devices)
    print(f"phase sharded W={workers} mesh=(data={n}, model=1) arch={ARCH} "
          f"reduced={reduced} seq={seq_len} steps={steps}", flush=True)
    cfg = get_config(ARCH)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    mesh = Mesh(np.asarray(devices).reshape(n, 1), ("data", "model"))
    shape = InputShape("smoke_train", seq_len, workers, "train")
    rules = rules_for(shape, False, mesh)
    scfg = SolverConfig(m=workers, T=steps, eta=1e-4, alpha=0.25,
                        aggregator="byzantine_sgd", attack="sign_flip",
                        mean_over_alive=True, guard_backend="dp_exact",
                        stats_dtype="bf16")
    opt = adamw(1e-4, grad_clip=1.0)
    train_step = build_train_step(model, opt, scfg)
    stream = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=seq_len, seed=0)
    init_key, mask_key, loop_key = jax.random.split(jax.random.PRNGKey(0), 3)
    rank = byz_rank(mask_key, workers)

    def step_fn(state, batch, rank, key):
        with use_logical_rules(rules, mesh):
            return train_step(state, batch, rank, key)

    def run(put, compiled):
        state = put(init_train_state(model, opt, scfg, init_key), "state")
        rk = put(rank, "rank")
        out = []
        for i in range(steps):
            batch = put(make_worker_batch(stream, workers, 1, i), "batch")
            key = put(jax.random.fold_in(loop_key, i), "key")
            t0 = time.perf_counter()
            state, ms = compiled(state, batch, rk, key)
            alive = np.asarray(state.prev_alive)
            out.append((float(ms["loss_good_workers"]), alive,
                        int(ms["byz_alive"]), int(ms["good_filtered"]),
                        time.perf_counter() - t0))
        return state, out

    # sharded: the dry-run's placement on the real devices
    c0 = smoke.compile_s
    sds = make_train_specs(model, scfg, "adamw", shape, rules, mesh)
    # the state leaves as it entered, so the next step takes it as is
    state_out = jax.tree_util.tree_map(lambda s: s.sharding, sds[0])
    compiled = (jax.jit(step_fn, donate_argnums=0,
                        out_shardings=(state_out, NamedSharding(mesh, P())))
                .lower(*sds).compile())
    spec_of = dict(zip(("state", "batch", "rank", "key"), sds))

    def put_sharded(tree, what):
        return jax.device_put(tree, jax.tree_util.tree_map(
            lambda s: s.sharding, spec_of[what]))

    state, sharded = run(put_sharded, compiled)
    hlo = compiled.as_text()
    colls = {op: hlo.count(op) for op in ("all-reduce", "all-gather",
                                          "reduce-scatter", "all-to-all",
                                          "collective-permute")}
    spread = {d.id for leaf in jax.tree_util.tree_leaves(state)
              for d in leaf.sharding.device_set}
    big = max(jax.tree_util.tree_leaves(state.guard), key=lambda a: a.size)
    b_devs = sorted({s.device.id for s in big.addressable_shards})
    peaks = [smoke.peak_bytes(d) for d in devices]
    print(f"  sharded compile_s={smoke.compile_s - c0:.2f} "
          f"step_s={[round(r[4], 4) for r in sharded]} "
          f"collectives={colls} state_devices={sorted(spread)} "
          f"B{tuple(big.shape)}_shard_devices={b_devs} "
          f"peak_bytes_in_use={peaks}", flush=True)
    del state

    # reference: the identical step on one device
    c0 = smoke.compile_s
    one = SingleDeviceSharding(devices[0])
    plain = jax.jit(train_step, donate_argnums=0)
    _, single = run(lambda t, _: jax.device_put(t, one), plain)
    print(f"  one-device compile_s={smoke.compile_s - c0:.2f} "
          f"step_s={[round(r[4], 4) for r in single]}", flush=True)

    for i, (s, r) in enumerate(zip(sharded, single)):
        print(f"  step {i} loss sharded={s[0]:.6f} one-device={r[0]:.6f} "
              f"alive sharded={s[1].astype(int).tolist()} "
              f"one-device={r[1].astype(int).tolist()} "
              f"byz_alive={s[2]}/{r[2]} good_filtered={s[3]}/{r[3]}",
              flush=True)
        smoke.check(bool(np.array_equal(s[1], r[1])),
                    f"step {i}: alive masks identical")
        smoke.check(math.isfinite(s[0]) and
                    abs(s[0] - r[0]) <= LOSS_RTOL * abs(r[0]),
                    f"step {i}: loss within rtol {LOSS_RTOL}")
    smoke.check(sum(colls.values()) > 0, "sharded step holds collectives")
    smoke.check(len(spread) == n, f"sharded state spans {n} devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this check runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC} holds no repro package", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = devices[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)

    smoke = Smoke(jax)
    t0 = time.perf_counter()
    if args.chips == 4:
        smoke.run("sharded", sharded_phase, devices[:4])
    else:
        smoke.run("guard_kernels", guard_phase)
        v_est = smoke.run("trainer[dp_exact]", trainer_phase, "dp_exact")
        if smoke.check(v_est is not None and math.isfinite(v_est)
                       and v_est > 0, f"dp_exact reported a usable v_est "
                                      f"({v_est})"):
            smoke.run("trainer[fused]", trainer_phase, "fused",
                      guard_v=v_est)
    print(f"total compile_s={smoke.compile_s:.2f} "
          f"wall_s={time.perf_counter() - t0:.2f}", flush=True)
    if smoke.failures:
        print(f"chip_smoke: {len(smoke.failures)} check(s) failed: "
              f"{smoke.failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
