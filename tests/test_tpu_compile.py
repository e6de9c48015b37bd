"""Compile-only tests: the guard's Pallas kernels through the TPU compiler.

The Pallas interpreter, which every other kernel test runs, does not see
tiling, layout or VMEM limits; the TPU compiler does, and it is installed
here even where no chip is attached.  Each test compiles one kernel at the
guard cell's shape (m=32 workers, d=2^20) for one chip of a *described*
v5e:2x2 topology and asserts that the program holds the Mosaic kernel
(``tpu_custom_call``) — nothing runs.

Only one process may load the TPU library at a time, so the topology is
described inside a module-scoped fixture (never while a module is
imported), and these tests stay in this one file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.sharding import SingleDeviceSharding

from repro.kernels import countsketch, fused_guard, robust_reduce
from repro.kernels.gradgen import GEN_NPARAMS

M, D, D_BLOCK = 32, 1 << 20, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # the persistent compile cache cannot read back entries for a chip
    # that is not attached; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _gen_args(s):
    return (_sds(s, (D,)), _sds(s, (D,)), _sds(s, (D,)), _sds(s, (D,)),
            _sds(s, (M, 2), jnp.uint32), _sds(s, (M,)),
            _sds(s, (M,), jnp.int32), _sds(s, (GEN_NPARAMS,)))


@pytest.mark.parametrize("dtype,sanitize", [
    (jnp.float32, False), (jnp.bfloat16, False), (jnp.float32, True)])
def test_fused_guard_compiles(one_chip, dtype, sanitize):
    """The one-pass sweep; HBM traffic is the 3·m·d·e of DESIGN.md §5."""
    e = jnp.dtype(dtype).itemsize
    compiled = _compile(
        functools.partial(fused_guard.fused_guard_pallas, d_block=D_BLOCK,
                          sanitize=sanitize),
        _sds(one_chip, (M, D), dtype), _sds(one_chip, (M, D), dtype),
        _sds(one_chip, (D,), dtype))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    assert mem.argument_size_in_bytes == 2 * M * D * e + D * e
    # B_new (m·d·e) plus the tile-padded (m, m), (m,) accumulators
    assert 0 < mem.output_size_in_bytes - M * D * e <= 2 * M * 128 * 4 + 4096


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_guard_fits_vmem_budget(one_chip, monkeypatch, dtype):
    """DESIGN.md §5's VMEM budget, 3·m·d_blk·e + 2·m²·4 bytes per grid
    step, double-buffered by the pipeline, plus a working set of four f32
    strips (g and B upcast in VMEM, and the bf16 high/low halves of both
    that the f32-precision MXU passes read): the sweep compiles with room
    for five working strips and is refused with room for three, so that
    accounting is the one that binds."""
    from jax.experimental.pallas import tpu as pltpu

    e = jnp.dtype(dtype).itemsize
    budget = 3 * M * D_BLOCK * e + 2 * M * M * 4
    strip = M * D_BLOCK * 4
    args = (_sds(one_chip, (M, D), dtype), _sds(one_chip, (M, D), dtype),
            _sds(one_chip, (D,), dtype))
    call = pl.pallas_call

    def compile_under(limit):
        monkeypatch.setattr(pl, "pallas_call", functools.partial(
            call, compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=int(limit))))
        jax.clear_caches()   # the kernel's trace holds its compiler params
        try:
            return _compile(functools.partial(
                fused_guard.fused_guard_pallas, d_block=D_BLOCK), *args)
        finally:
            monkeypatch.setattr(pl, "pallas_call", call)
            jax.clear_caches()

    compile_under(2 * budget + 5 * strip)
    with pytest.raises(Exception, match="vmem"):
        compile_under(2 * budget + 3 * strip)


@pytest.mark.parametrize("dtype,sanitize", [
    (jnp.float32, False), (jnp.bfloat16, True)])
def test_filtered_mean_compiles(one_chip, dtype, sanitize):
    """ξ of the ``fused`` guard backend (a 2-D (1, m) @ (m, d_blk) dot)."""
    _compile(functools.partial(robust_reduce.filtered_mean_pallas,
                               denom=1.0, d_block=D_BLOCK, sanitize=sanitize),
             _sds(one_chip, (M, D), dtype), _sds(one_chip, (M,)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_guard_gen_compiles(one_chip, dtype):
    """The sweep with its gradient strips generated in-kernel."""
    _compile(functools.partial(fused_guard.fused_guard_gen_pallas,
                               d_block=D_BLOCK),
             _sds(one_chip, (M, D), dtype), _sds(one_chip, (D,), dtype),
             *_gen_args(one_chip))


@pytest.mark.parametrize("stats_dtype", ["float32", "bfloat16"])
def test_gen_xi_compiles(one_chip, stats_dtype):
    _compile(functools.partial(fused_guard.gen_xi_pallas, d_block=D_BLOCK,
                               stats_dtype=stats_dtype),
             _sds(one_chip, (M,)), _sds(one_chip, (M,)),
             *_gen_args(one_chip))


def test_countsketch_compiles(one_chip):
    _compile(functools.partial(countsketch.countsketch_pallas, k=1024,
                               d_block=8192),
             _sds(one_chip, (M, D)))
