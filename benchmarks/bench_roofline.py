"""Deliverable (g) — render the roofline table from the dry-run records
in experiments/dryrun/*.json (written by repro.launch.dryrun)."""
from __future__ import annotations

import glob
import json
import os

from benchmarks.common import emit
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    records = []
    for path in sorted(glob.glob("experiments/dryrun/*.json")):
        with open(path) as f:
            records.append(json.load(f))
    if not records:
        emit("roofline/none", 0.0, "run `python -m repro.launch.dryrun` first")
        return
    for r in records:
        rl = r["roofline"]
        tag = f"{r['arch']}/{r['shape']}/{'mp' if r['multi_pod'] else 'sp'}"
        if r.get("opts"):
            tag += "/opt-" + "-".join(sorted(r["opts"]))
        dominant = max(rl["t_compute_s"], rl["t_memory_s"], rl["t_collective_s"])
        emit(
            f"roofline/{tag}", dominant * 1e6,
            f"bottleneck={rl['bottleneck']},c={rl['t_compute_s']*1e3:.1f}ms,"
            f"m={rl['t_memory_s']*1e3:.1f}ms,n={rl['t_collective_s']*1e3:.1f}ms,"
            f"useful={rl['useful_ratio']:.2%},fits={r['memory']['fits_hbm_16g']},"
            f"peakGB={r['memory']['peak_bytes']/1e9:.1f}",
        )


if __name__ == "__main__":
    enable_compile_cache()
    main()
