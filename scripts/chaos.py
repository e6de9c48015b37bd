"""Chaos-injection harness (DESIGN.md §15) → ``CHAOS_report.json``.

Runs the fault matrix the checkpoint layer and the sanitize stage promise
to survive, end-to-end through the real entry points (``repro.launch.train``
subprocesses for the crash cases, ``run_campaign`` in-process for the
value-corruption cases):

==================  =======================================================
``kill_resume``     SIGKILL the trainer right after its first periodic
                    checkpoint lands, resume with ``--resume`` — the final
                    checkpoint must be **bit-identical** to an
                    uninterrupted run's
``truncate``        truncate the newest checkpoint file (torn write);
                    ``latest_step`` must skip it and resume from the
                    previous complete one, still bit-identical at the end
``corrupt``         flip a stored leaf under an intact container + stale
                    checksum (silent bit rot); restore must quarantine the
                    file (``*.corrupt``) with a warning and degrade to the
                    previous valid checkpoint, still bit-identical
``sigterm``         SIGTERM mid-run (preemption notice); the trainer must
                    exit cleanly, flushing a resumable final checkpoint
                    within the grace budget, and resume to bit-parity
``nonfinite``       mini campaign with NaN/Inf/bitflip fault plans over
                    every guard backend under ``sanitize="quarantine"`` —
                    every leaderboard gap finite, victims filtered
==================  =======================================================

It is a CPU harness: the trainer children run with ``JAX_PLATFORMS=cpu``
whatever the parent's environment says.

Bit-parity is the strong form of the resume-equals-uninterrupted contract:
the comparison is over the raw stored arrays of the final checkpoint, not a
float tolerance.

Usage::

    PYTHONPATH=src python scripts/chaos.py --mini          # CI tier-2 shape
    PYTHONPATH=src python scripts/chaos.py --steps 48      # bigger sweep
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train_cmd(ckpt_dir: str, steps: int, d_model: int, *extra: str) -> list[str]:
    return [
        sys.executable, "-m", "repro.launch.train",
        "--arch", "mamba2-130m", "--reduced",
        "--workers", "4", "--per-worker-batch", "1",
        "--seq-len", "32", "--d-model", str(d_model),
        "--steps", str(steps), "--log-every", "4",
        "--alpha", "0.25", "--attack", "sign_flip",
        "--guard-backend", "dp_exact", "--seed", "0",
        "--ckpt-dir", ckpt_dir, *extra,
    ]


def _env() -> dict:
    # a CPU harness: the trainer children always run on the CPU.  On a
    # machine with a chip this parent may hold it (the nonfinite case runs
    # in-process), and a child that also asked for it would fail or hang
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _run(cmd: list[str], timeout: int = 900) -> subprocess.CompletedProcess:
    return subprocess.run(cmd, env=_env(), cwd=REPO, capture_output=True,
                          text=True, timeout=timeout)


def _wait_for_ckpt(ckpt_dir: str, proc: subprocess.Popen,
                   timeout: float = 600.0) -> str | None:
    """Poll until the first committed ``ckpt_*.npz`` appears (or the
    process exits / times out).  Returns the path or None."""
    t0 = time.time()
    while time.time() - t0 < timeout:
        if os.path.isdir(ckpt_dir):
            names = sorted(f for f in os.listdir(ckpt_dir)
                           if f.startswith("ckpt_") and f.endswith(".npz"))
            if names:
                return os.path.join(ckpt_dir, names[0])
        if proc.poll() is not None:
            return None
        time.sleep(0.25)
    return None


def _final_ckpt_arrays(ckpt_dir: str, step: int) -> dict:
    import numpy as np
    path = os.path.join(ckpt_dir, f"ckpt_{step:08d}.npz")
    with np.load(path, allow_pickle=False) as data:
        return {k: np.array(data[k]) for k in data.files}


def _bit_identical(a: dict, b: dict) -> bool:
    import numpy as np

    def eq(x, y):
        if x.dtype != y.dtype or x.shape != y.shape:
            return False
        # equal_nan only exists for float dtypes; exact compare elsewhere
        if np.issubdtype(x.dtype, np.floating):
            return bool(np.array_equal(x, y, equal_nan=True))
        return bool(np.array_equal(x, y))

    return set(a) == set(b) and all(eq(a[k], b[k]) for k in a)


def case_baseline(work: str, steps: int, d_model: int) -> tuple[dict, dict]:
    """Uninterrupted reference run; its final checkpoint is the parity
    target for every crash case."""
    ckpt = os.path.join(work, "baseline")
    p = _run(_train_cmd(ckpt, steps, d_model, "--ckpt-every", "8"))
    ok = p.returncode == 0
    arrays = _final_ckpt_arrays(ckpt, steps) if ok else {}
    return {"ok": ok, "detail": p.stderr[-2000:] if not ok else ""}, arrays


def _resume_and_compare(ckpt: str, steps: int, d_model: int,
                        baseline: dict, expect_warn: bool = False) -> dict:
    p = _run(_train_cmd(ckpt, steps, d_model, "--ckpt-every", "8", "--resume"))
    if p.returncode != 0:
        return {"ok": False, "detail": f"resume failed: {p.stderr[-2000:]}"}
    out = {"ok": True, "resumed_line": next(
        (ln for ln in p.stdout.splitlines() if ln.startswith("resumed")), "")}
    if expect_warn and "quarantined" not in p.stderr:
        return {"ok": False, "detail": "expected a quarantine warning"}
    final = _final_ckpt_arrays(ckpt, steps)
    if not _bit_identical(final, baseline):
        return {"ok": False, "detail": "final checkpoint differs from "
                                       "uninterrupted run (bit-parity broken)"}
    out["bit_identical"] = True
    return out


def case_kill_resume(work: str, steps: int, d_model: int, baseline: dict) -> dict:
    """SIGKILL right after the first periodic checkpoint commits."""
    ckpt = os.path.join(work, "kill")
    proc = subprocess.Popen(_train_cmd(ckpt, steps, d_model, "--ckpt-every", "8"),
                            env=_env(), cwd=REPO,
                            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    first = _wait_for_ckpt(ckpt, proc)
    if first is None:
        proc.kill()
        return {"ok": False, "detail": "no checkpoint appeared before exit"}
    proc.send_signal(signal.SIGKILL)
    proc.wait()
    return _resume_and_compare(ckpt, steps, d_model, baseline)


def _seed_two_checkpoints(work: str, name: str, steps: int, d_model: int) -> str | None:
    """A prefix run that leaves ≥ 2 committed checkpoints to damage."""
    ckpt = os.path.join(work, name)
    p = _run(_train_cmd(ckpt, steps, d_model, "--ckpt-every", "8",
                        "--stop-after", "16"))
    if p.returncode != 0:
        return None
    names = sorted(f for f in os.listdir(ckpt)
                   if f.startswith("ckpt_") and f.endswith(".npz"))
    return ckpt if len(names) >= 2 else None


def case_truncate(work: str, steps: int, d_model: int, baseline: dict) -> dict:
    """Torn write: the newest checkpoint is half a file."""
    ckpt = _seed_two_checkpoints(work, "truncate", steps, d_model)
    if ckpt is None:
        return {"ok": False, "detail": "could not seed two checkpoints"}
    latest = sorted(f for f in os.listdir(ckpt)
                    if f.startswith("ckpt_") and f.endswith(".npz"))[-1]
    path = os.path.join(ckpt, latest)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 2)
    # truncated zip = incomplete unit: latest_step must not advertise it,
    # so the resume silently starts from the previous complete checkpoint
    return _resume_and_compare(ckpt, steps, d_model, baseline)


def case_corrupt(work: str, steps: int, d_model: int, baseline: dict) -> dict:
    """Silent bit rot: intact container, one leaf no longer matches its
    manifest checksum — must quarantine + degrade, not crash."""
    import numpy as np
    ckpt = _seed_two_checkpoints(work, "corrupt", steps, d_model)
    if ckpt is None:
        return {"ok": False, "detail": "could not seed two checkpoints"}
    latest = sorted(f for f in os.listdir(ckpt)
                    if f.startswith("ckpt_") and f.endswith(".npz"))[-1]
    path = os.path.join(ckpt, latest)
    with np.load(path, allow_pickle=False) as data:
        arrays = {k: np.array(data[k]) for k in data.files}
    flat = arrays["leaf_0"].reshape(-1)
    flat[: max(1, flat.size // 8)] = flat[: max(1, flat.size // 8)] + 1
    with open(path, "wb") as f:
        np.savez(f, **arrays)  # container valid, checksum now stale
    res = _resume_and_compare(ckpt, steps, d_model, baseline, expect_warn=True)
    if res.get("ok") and not any(f.endswith(".corrupt")
                                 for f in os.listdir(ckpt)):
        return {"ok": False, "detail": "corrupt file was not quarantined"}
    return res


def case_sigterm(work: str, steps: int, d_model: int, baseline: dict) -> dict:
    """Preemption notice: SIGTERM after the first periodic checkpoint; the
    trainer must exit 0 with a flushed, resumable checkpoint."""
    ckpt = os.path.join(work, "sigterm")
    proc = subprocess.Popen(_train_cmd(ckpt, steps, d_model, "--ckpt-every", "8"),
                            env=_env(), cwd=REPO,
                            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                            text=True)
    first = _wait_for_ckpt(ckpt, proc)
    if first is None:
        proc.kill()
        return {"ok": False, "detail": "no checkpoint appeared before exit"}
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=300)
    except subprocess.TimeoutExpired:
        proc.kill()
        return {"ok": False, "detail": "trainer ignored SIGTERM (grace "
                                       "budget exceeded)"}
    if proc.returncode != 0:
        return {"ok": False, "detail": f"exit code {proc.returncode} after "
                                       "SIGTERM (expected graceful flush)"}
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.checkpoint import latest_step
    flushed = latest_step(ckpt)
    if flushed is None:
        return {"ok": False, "detail": "no complete checkpoint after SIGTERM"}
    res = ({"ok": True, "note": "run completed before the signal landed"}
           if flushed >= steps else
           _resume_and_compare(ckpt, steps, d_model, baseline))
    res["flushed_step"] = int(flushed)
    return res


def case_nonfinite(steps: int) -> dict:
    """NaN/Inf/bitflip fault sweep through one jitted campaign: every guard
    backend returns finite leaderboard rows and filters the victims."""
    sys.path.insert(0, os.path.join(REPO, "src"))
    import numpy as np
    from repro.core.solver import SolverConfig
    from repro.data.problems import make_quadratic_problem
    from repro.scenarios import (
        expand_grid,
        fault_bitflip,
        fault_inf_rows,
        fault_nan_rows,
        fault_none,
        run_campaign,
        scenario_static,
    )

    quad = make_quadratic_problem(d=24, sigma=1.0, L=8.0, V=1.0, seed=1)
    cfg = SolverConfig(m=8, T=steps, eta=0.05, alpha=0.25,
                       aggregator="byzantine_sgd", attack="sign_flip",
                       sanitize="quarantine")
    grid = expand_grid(
        [("static", scenario_static("sign_flip"))], [0.125], [0, 1],
        faults=[("none", fault_none()),
                ("nan", fault_nan_rows(0.25)),
                ("inf", fault_inf_rows(0.25, period=2)),
                ("bitflip", fault_bitflip(0.25, start_step=4))],
    )
    result = run_campaign(
        quad, cfg, grid, ["byzantine_sgd", "mean", "coordinate_median"],
        backends=["dense", "fused", "dp_exact", "dp_sketch"],
    )
    cells, bad = 0, []
    for name, stats in result.stats.items():
        for field in ("gap_avg", "gap_final"):
            vals = np.asarray(getattr(stats, field))
            cells += vals.size
            if not np.all(np.isfinite(vals)):
                bad.append(f"{name}.{field}")
    # the guard must count fault victims toward the realized Byzantine set
    guard = result.stats["byzantine_sgd@dense"]
    n_ever = np.asarray(guard.n_byz_ever).reshape(2, 4)  # (seed, fault)
    filtered = bool(np.all(n_ever[:, 1:] > n_ever[:, :1]))
    return {"ok": not bad and filtered, "cells_checked": cells,
            "non_finite_cells": bad,
            "victims_filtered": filtered,
            "variants": sorted(result.stats)}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=24,
                    help="trainer steps per crash case (≥ 17 so two "
                         "periodic checkpoints land before completion)")
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--mini", action="store_true",
                    help="CI tier-2 shape (same as the defaults today; "
                         "pinned so local sweeps can grow without moving CI)")
    ap.add_argument("--out", default=os.path.join(REPO, "CHAOS_report.json"))
    ap.add_argument("--keep-work", action="store_true",
                    help="keep the scratch checkpoint directories")
    args = ap.parse_args()
    steps, d_model = args.steps, args.d_model

    report: dict = {"steps": steps, "d_model": d_model, "cases": {}}
    work = tempfile.mkdtemp(prefix="chaos_")
    try:
        t0 = time.time()
        base_res, base_arrays = case_baseline(work, steps, d_model)
        report["cases"]["baseline"] = base_res
        if base_res["ok"]:
            for name, fn in [("kill_resume", case_kill_resume),
                             ("truncate", case_truncate),
                             ("corrupt", case_corrupt),
                             ("sigterm", case_sigterm)]:
                t = time.time()
                res = fn(work, steps, d_model, base_arrays)
                res["wall_s"] = round(time.time() - t, 2)
                report["cases"][name] = res
                print(f"{name}: {'PASS' if res['ok'] else 'FAIL'} "
                      f"({res['wall_s']}s)  {res.get('detail', '')}")
        t = time.time()
        res = case_nonfinite(steps=20)
        res["wall_s"] = round(time.time() - t, 2)
        report["cases"]["nonfinite"] = res
        print(f"nonfinite: {'PASS' if res['ok'] else 'FAIL'} "
              f"({res['wall_s']}s)")
        report["wall_s"] = round(time.time() - t0, 2)
    finally:
        if args.keep_work:
            print(f"work dir kept: {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)

    report["ok"] = all(c.get("ok") for c in report["cases"].values())
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}  (matrix {'GREEN' if report['ok'] else 'RED'})")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
