import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod AOT dry-run: lower + compile every (arch × input-shape) on the
production mesh, record memory/cost analysis + roofline terms.

MUST be run as a module entry point (``python -m repro.launch.dryrun``) —
the XLA_FLAGS line above executes before any jax import, giving this
process 512 placeholder CPU devices so ``jax.make_mesh`` can build the
production topology. Nothing here allocates device memory: inputs and
states are ShapeDtypeStructs.

Per combination we emit a JSON record under ``--out-dir`` with:
  * memory_analysis (per-device argument/output/temp bytes),
  * cost_analysis (per-device FLOPs / bytes accessed),
  * collective bytes by kind (parsed from partitioned HLO),
  * the three roofline terms + dominant bottleneck (§Roofline).

Shape→step mapping: train_4k → train_step (Byzantine guard included);
prefill_32k → prefill; decode_32k / long_500k → serve_step.
``long_500k`` uses each arch's sub-quadratic path (SSM state, MLA latent
cache, sliding-window ring cache for pure-attention archs — see DESIGN.md).
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.configs.base import InputShape, ModelConfig
from repro.core.solver import SolverConfig
from repro.distributed.sharding import use_logical_rules
from repro.distributed.specs import (
    make_prefill_specs,
    make_serve_specs,
    make_train_specs,
    rules_for,
)
from repro.distributed.trainer import build_serve_step, build_train_step, init_train_state
from repro.launch.mesh import make_production_mesh, n_workers
from repro.models import build_model
from repro.optim import adamw
from repro.roofline import roofline_from_compiled
from repro.roofline.hw import TPU_V5E

LONG_CONTEXT_WINDOW = 4096   # ring-cache window for pure-attention archs @500k


def arch_variant_for_shape(cfg: ModelConfig, shape: InputShape) -> tuple[ModelConfig, str]:
    """long_500k: keep native sub-quadratic paths (ssm/hybrid/MLA), switch
    pure-GQA archs to a sliding-window ring cache (documented variant)."""
    if shape.name != "long_500k":
        return cfg, "native"
    if cfg.ssm_state > 0 and cfg.attn_period == 0:
        return cfg, "native-ssm"            # mamba2: O(1) state
    if cfg.attn_period > 0:
        return cfg, "native-hybrid"         # jamba: mamba + few attn layers
    if cfg.use_mla:
        return cfg, "native-mla-latent"     # deepseek: (L, kv_lora+r) cache
    if cfg.sliding_window:
        return cfg, "native-swa"            # starcoder2: already windowed
    return dataclasses.replace(cfg, sliding_window=LONG_CONTEXT_WINDOW), "swa-variant"


def _kind(shape: InputShape) -> str:
    return {"train": "train", "prefill": "prefill", "decode": "decode"}[shape.kind]


def lower_one(arch: str, shape_name: str, multi_pod: bool, guard_mode: str = "sketch",
              mesh=None, cfg_map=None, shape_map=None, opts: tuple = ()):
    """Lower + compile one (arch, shape, mesh) combination; returns record dict.

    ``mesh`` / ``cfg_map`` / ``shape_map`` exist for the test suite (tiny
    meshes + reduced configs exercise the identical code path).

    ``opts`` — §Perf levers (EXPERIMENTS.md records each):
      'lp_guard'  — bf16 guard statistics: sets the solver-wide
                    ``SolverConfig.stats_dtype='bf16'`` axis (DESIGN.md §5
                    Numerics) — the dry-run perf lever and the solver
                    config name the same knob (no f32 grad copies, halved
                    all-gather bytes, bf16 B storage)
      'no_sp'     — disable act_seq sequence parallelism for train
      'donate'    — donate the train state (aliased in-place update)
      'kv_quant'  — int8 KV cache for decode shapes (serving lever)
      'exact_guard' — paper-faithful exact-mode guard (vs default sketch):
                    quantifies the sketch's communication savings
      'chunk512' / 'chunk2048' — attention KV-chunk size sweep
    """
    shape = INPUT_SHAPES[shape_name]
    if shape_map is not None:
        shape = shape_map(shape)
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_desc = "x".join(str(s) for s in mesh.devices.shape) + ("(2pod)" if multi_pod else "")
    n_chips = mesh.devices.size
    cfg, variant = arch_variant_for_shape(get_config(arch), shape)
    if cfg_map is not None:
        cfg = cfg_map(cfg)
    rules = rules_for(shape, multi_pod, mesh)
    if "no_sp" in opts:
        rules["act_seq"] = None
    if "kv_quant" in opts and shape.is_decode:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    if "exact_guard" in opts:
        guard_mode = "exact"
    if "chunk512" in opts:
        cfg = dataclasses.replace(cfg, attn_chunk=512)
    if "chunk2048" in opts:
        cfg = dataclasses.replace(cfg, attn_chunk=2048)
    model = build_model(cfg)
    W = n_workers(mesh)

    t0 = time.time()
    with use_logical_rules(rules, mesh):
        if shape.kind == "train":
            # the guard rides the unified SolverConfig axes (DESIGN.md §10):
            # the historical exact/sketch modes are the dp_exact/dp_sketch
            # guard backends on the tree-harness flat view, and 'lp_guard'
            # is the stats_dtype='bf16' point of the §5 precision axis
            scfg = SolverConfig(
                m=W, T=10_000, eta=1e-4, alpha=0.25,
                aggregator="byzantine_sgd", attack="none",
                mean_over_alive=True,
                guard_backend={"exact": "dp_exact", "sketch": "dp_sketch"}[guard_mode],
                stats_dtype="bf16" if "lp_guard" in opts else "f32",
            )
            optimizer = adamw(1e-4, grad_clip=1.0)
            train_step = build_train_step(model, optimizer, scfg)
            state_sds, batch_sds, rank_sds, rng_sds = make_train_specs(
                model, scfg, "adamw", shape, rules, mesh
            )

            def step_fn(state, batch, rank, rng):
                with use_logical_rules(rules, mesh):
                    return train_step(state, batch, rank, rng)

            donate = (0,) if "donate" in opts else ()
            lowered = jax.jit(step_fn, donate_argnums=donate).lower(
                state_sds, batch_sds, rank_sds, rng_sds)
        elif shape.kind == "prefill":
            params_sds, batch_sds = make_prefill_specs(model, shape, rules, mesh)

            def step_fn(params, batch):
                with use_logical_rules(rules, mesh):
                    return model.prefill(params, batch, cache_len=shape.seq_len)

            lowered = jax.jit(step_fn).lower(params_sds, batch_sds)
        else:  # decode
            serve_step = build_serve_step(model)
            params_sds, cache_sds, token_sds = make_serve_specs(model, shape, rules, mesh)

            def step_fn(params, cache, tok):
                with use_logical_rules(rules, mesh):
                    return serve_step(params, cache, tok)

            lowered = jax.jit(step_fn).lower(params_sds, cache_sds, token_sds)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    report = roofline_from_compiled(
        compiled, arch, shape, mesh_desc, n_chips, cfg, TPU_V5E
    )
    mem = compiled.memory_analysis()
    record = {
        "arch": arch,
        "shape": shape.name,
        "mesh": mesh_desc,
        "multi_pod": multi_pod,
        "variant": variant,
        "n_chips": n_chips,
        "n_workers": W if shape.kind == "train" else None,
        "guard_mode": guard_mode if shape.kind == "train" else None,
        "stats_dtype": (("bf16" if "lp_guard" in opts else "f32")
                        if shape.kind == "train" else None),
        "opts": list(opts),
        "_hlo_text": compiled.as_text(),
        "lower_s": t_lower,
        "compile_s": t_compile,
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            "peak_bytes": report.peak_memory_bytes,
            "fits_hbm_16g": report.fits_hbm,
        },
        "cost": {
            "hlo_flops_per_device": report.hlo_flops,
            "hlo_bytes_per_device": report.hlo_bytes,
        },
        "collectives": {
            "total_bytes_per_device": report.collective_bytes,
            "by_kind": report.collective_by_kind,
        },
        "roofline": {
            "t_compute_s": report.t_compute,
            "t_memory_s": report.t_memory,
            "t_collective_s": report.t_collective,
            "bottleneck": report.bottleneck,
            "model_flops": report.model_flops,
            "useful_ratio": report.useful_ratio,
        },
    }
    return record, report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one input shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--guard-mode", default="sketch", choices=["sketch", "exact"])
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--save-hlo", action="store_true",
                    help="persist gzipped partitioned HLO next to the JSON")
    ap.add_argument("--opt", action="append", default=[],
                    choices=["lp_guard", "no_sp", "donate", "kv_quant",
                             "exact_guard", "chunk512", "chunk2048"],
                    help="§Perf levers; may repeat")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    os.makedirs(args.out_dir, exist_ok=True)

    failures = []
    for arch in archs:
        for shape in shapes:
            opt_tag = ("__opt-" + "-".join(sorted(set(args.opt)))) if args.opt else ""
            tag = f"{arch}__{shape}__{'multipod' if args.multi_pod else 'singlepod'}{opt_tag}"
            out_path = os.path.join(args.out_dir, tag + ".json")
            if args.skip_existing and os.path.exists(out_path):
                print(f"[skip] {tag}")
                continue
            try:
                record, report = lower_one(arch, shape, args.multi_pod, args.guard_mode, opts=tuple(args.opt))
                hlo_text = record.pop("_hlo_text", None)
                with open(out_path, "w") as f:
                    json.dump(record, f, indent=2)
                if args.save_hlo and hlo_text:
                    import gzip
                    with gzip.open(out_path.replace(".json", ".hlo.gz"), "wt") as f:
                        f.write(hlo_text)
                print(f"[ok]   {report.row()}  (compile {record['compile_s']:.0f}s)")
            except Exception as e:
                failures.append((tag, repr(e)))
                with open(out_path + ".failed", "w") as f:
                    f.write(traceback.format_exc())
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
    if failures:
        print(f"\n{len(failures)} failures:")
        for tag, err in failures:
            print(" ", tag, err[:200])
        raise SystemExit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
