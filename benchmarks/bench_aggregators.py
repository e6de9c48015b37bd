"""Table 1 — per-iteration per-machine work: wall time per aggregation call
vs (m, d).  Confirms the complexity separation the paper argues in §1.4:
Krum's O(m²(d + log m)) vs the guard's O(md) + O(m²) scalar work, and the
Pallas kernel variants of the reductions."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.core.aggregators import get_aggregator
from repro.core.byzantine_sgd import ByzantineGuard, GuardConfig
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    key = jax.random.PRNGKey(0)
    for m, d in [(16, 1 << 14), (16, 1 << 17), (64, 1 << 14)]:
        x = jax.random.normal(key, (m, d), jnp.float32)

        for name in ["mean", "coordinate_median", "trimmed_mean", "krum",
                      "geometric_median"]:
            kwargs = {"n_byzantine": m // 4} if name == "krum" else (
                {"trim_fraction": 0.25} if name == "trimmed_mean" else {})
            fn = jax.jit(get_aggregator(name, **kwargs))
            us = time_fn(fn, x, warmup=1, iters=5)
            emit(f"agg/{name}/m{m}/d{d}", us, f"throughput_GBps={m*d*4/us/1e3:.2f}")

        # the guard's full step (martingales + filter + masked mean)
        guard = ByzantineGuard(GuardConfig(m=m, T=100, V=4.0, D=10.0))
        state = guard.init(d)
        xk = jnp.zeros((d,))
        step = jax.jit(lambda s, g: guard.step(s, g, xk, xk))
        us = time_fn(step, state, x, warmup=1, iters=5)
        emit(f"agg/byzantine_sgd_step/m{m}/d{d}", us,
             f"throughput_GBps={m*d*4/us/1e3:.2f}")

    # Pallas kernels: interpret mode on CPU executes the kernel body in
    # Python — time one small shape per kernel (wall time on CPU is NOT the
    # TPU projection; the roofline suite covers that)
    m, d = 16, 1 << 12
    x = jax.random.normal(key, (m, d), jnp.float32)
    us = time_fn(lambda y: ops.gram(y, d_block=1024), x, warmup=1, iters=3)
    emit(f"kernel/gram/m{m}/d{d}", us, "interpret-mode")
    us = time_fn(lambda y: ops.coordinate_median(y, d_block=1024), x, warmup=1, iters=3)
    emit(f"kernel/coordinate_median/m{m}/d{d}", us, "interpret-mode")
    mask = jnp.ones((m,), bool)
    us = time_fn(lambda y: ops.filtered_mean(y, mask, float(m), d_block=1024), x,
                 warmup=1, iters=3)
    emit(f"kernel/filtered_mean/m{m}/d{d}", us, "interpret-mode")
    us = time_fn(lambda y: ops.countsketch(y, 256, d_block=1024), x, warmup=1, iters=3)
    emit(f"kernel/countsketch/m{m}/d{d}", us, "interpret-mode")


if __name__ == "__main__":
    enable_compile_cache()
    main()
