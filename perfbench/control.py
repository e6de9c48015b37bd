#!/usr/bin/env python3
"""Readings from which a cell's correctness limits are set, on the chip.

    python perfbench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 3 [--out FILE]

In one process, with one compiled chunk: for every seed, the program's
first chunk against the f32 reference (the lower readings); for the first
``--control-seeds`` seeds also the control (the reference at fp8, in the
program's place) and the reference with each planted fault (half of every
row left out of the loss; every row's first token altered where the stream
produces it) against the
same f32 reference (the upper readings).  A state left unchanged reads 1
on ``update_gap`` and needs no run.  Prints one line per run and, last,
the readings as JSON (also written to ``--out``).  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
# the control, and the planted faults, each a reference built with these options
VARIANTS = {"control_fp8": dict(precision="fp8"),
            "fault_half_batch": dict(fault="half_batch"),
            "fault_token": dict(fault="token")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from perfbench import compare, registry
    from perfbench.harness import Program
    from perfbench.reference.train import build, run
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    wl = registry.workload(args.workload)
    cfg = registry.config(wl["config"])
    tr = registry.traffic(wl["traffic"])
    fam = registry.reference_family(cfg["reference"])
    wb = cfg.get("reference_worker_batch")
    L = tr["log_every"]
    seeds = [int(s) for s in args.seeds.split(",")]
    prog = Program(cfg, tr)
    refs = {"f32": build(fam, cfg["model"], tr, worker_batch=wb)}
    out = {"workload": args.workload, "runs": []}
    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        prog.start(seed)
        readings = prog.first_chunk()
        del prog.state
        prog.weight_change(readings)
        ref = run(refs["f32"], seed, L)
        runs = {"program": readings}
        if n < args.control_seeds:
            for name, opts in VARIANTS.items():
                if name not in refs:
                    refs[name] = build(fam, cfg["model"], tr, worker_batch=wb, **opts)
                runs[name] = run(refs[name], seed, L)
        for name, r in runs.items():
            g = compare.gaps(r, ref)
            values = compare.numbers(r, ref, g)
            out["runs"].append({"seed": seed, "run": name, **values,
                                "steps": r["steps"], "gaps": g})
            print(f"seed {seed} {name}: {values}", flush=True)
            if name == "program":
                print(f"seed {seed} worst weights: {compare.worst_leaves(g)}", flush=True)
        out["runs"].append({"seed": seed, "run": "reference_norms", **{
            k: {w: compare.norm(a) for w, a in ref[k].items()} for k in ("m", "dx")}})
        del runs, readings
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s; reference steps "
              f"{ref['steps']}", flush=True)
        if args.out:    # after every seed, so that a run cut short keeps its readings
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text(json.dumps(out))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
