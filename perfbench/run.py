#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's trainer with weights made on the device from the seed,
compiles and warms its chunk program (set-up), trains whole chunks for
``--seconds`` and checks the first chunk against the plain reference.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` traces
the window with the profiler and reports its per-layer metrics.

Earlier lines of stdout report compilation, the chunk program's memory
analysis and the window's chunk times.  The last line of stdout is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
compared number beside its limit); the last lines of stderr repeat the
checks.  Without a TPU, with fewer chips than the cell asks for, or
without the repository's ``src/`` beside ``perfbench/``, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"perfbench: {ROOT / 'src'} holds no repro package", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from perfbench import registry

    bench = registry.benchmark(ROOT)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"perfbench: no workload {args.workload!r}; have {sorted(cells)}",
              file=sys.stderr)
        return 2

    # JAX's persistent compilation cache goes to the checkout's own
    # .jax_cache: a directory named in the environment may be another
    # checkout's too.  Dropped before JAX reads it, at import.
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    import jax

    devices = jax.devices()
    chips = cells[args.workload]["chips"]
    if devices[0].platform != "tpu":
        print(f"perfbench: no TPU (JAX found {devices[0].platform}); "
              "the benchmark runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"perfbench: the cell needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from perfbench.harness import run_cell
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    d = devices[0]
    print(f"device platform={d.platform} kind={d.device_kind} count={len(devices)} "
          f"compile_cache={cache}", flush=True)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          T_START, bench, log=lambda s: print(s, flush=True))
    except Exception:  # noqa: BLE001 — any fault ends the run with no result
        traceback.print_exc()
        return 1
    if result["device"]["memory_peak_bytes"] is None:
        print("perfbench: the device reports no peak_bytes_in_use", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    print(f"correct={result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
