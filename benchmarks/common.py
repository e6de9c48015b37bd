"""Benchmark utilities: timing, CSV emission, provenance-stamped JSON."""
from __future__ import annotations

import json
import time

import jax

from repro.obs.provenance import provenance_meta
from repro.roofline.hw import HwSpec, peaks_for


def time_fn(fn, *args, warmup: int = 2, iters: int = 10) -> float:
    """Median wall-time (µs) of fn(*args) with jax block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def chip_peaks() -> HwSpec | None:
    """Peak row of the chip this run is on; None off the TPU, where no
    device time is modelled (a CPU run states bytes only)."""
    dev = jax.devices()[0]
    return peaks_for(dev.device_kind) if dev.platform == "tpu" else None


def emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}")


def device_memory_stats() -> dict | None:
    """Peak / in-use device memory of the default device, in bytes —
    ``None`` when the platform does not report allocator statistics (CPU
    JAX usually does not; TPU/GPU do).  Best-effort by design: memory
    accounting must never be the reason a benchmark fails."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — platform-dependent, optional
        return None
    if not stats:
        return None
    keep = ("peak_bytes_in_use", "bytes_in_use", "largest_alloc_size",
            "bytes_limit", "pool_bytes")
    out = {k: int(v) for k, v in stats.items() if k in keep}
    return out or None


def write_json(path: str, record: dict) -> None:
    """Write a ``BENCH_*.json`` record with a provenance ``meta`` block
    (commit SHA, jax/jaxlib versions, device kind, timestamp — DESIGN.md
    §12) plus the device allocator's peak-memory counters where the
    platform reports them, so every benchmark artifact says which code on
    which machine produced it and how much device memory the run actually
    held.  An existing ``meta`` key is kept (caller stamped richer fields)
    but still gains the memory counters if it lacks them."""
    record.setdefault("meta", provenance_meta())
    mem = device_memory_stats()
    if mem is not None and isinstance(record.get("meta"), dict):
        record["meta"].setdefault("device_memory", mem)
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {path}")
