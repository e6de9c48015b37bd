"""One run of one cell: build the trainer, warm it, measure a window,
check its first steps against the plain reference.

The program under test is the trainer's chunked scan, built from its
public pieces as ``repro.launch.train.run_training`` builds it:
``build_train_step`` and ``init_train_state``, stepped by a donated
``jax.lax.scan`` over ``log_every`` steps, with each step's batch made on
the device by ``make_worker_batch`` and keys split as the launcher splits
them.  The seed, the Byzantine ranks and the loop key are arguments of the
compiled chunk, so one compiled program serves every seed.

Set-up (``setup_s``) runs from process start to the end of the first
chunk, which compiles and warms the one program the window drives and
whose steps are then compared with the reference.  The window runs whole
chunks, each ended by the transfer of its metrics to the host, until
``seconds`` have passed.
"""
from __future__ import annotations

import math
import tempfile
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import compare, registry
from perfbench import trace as trace_lib
from perfbench.peaks import peak_for
from perfbench.reference.train import reference_run
from repro.configs.base import ModelConfig
from repro.core.solver import SolverConfig, byz_rank
from repro.data.synthetic import SyntheticTokens, make_worker_batch
from repro.distributed.trainer import build_train_step, init_train_state
from repro.models import build_model
from repro.optim import adamw

SPAN = "perfbench/"          # prefix of the benchmark's own host spans


class CompileClock:
    """Seconds and count of compilations, from JAX's monitoring events."""

    def __init__(self):
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


class Program:
    """The cell's trainer: the compiled chunk, and the state of the run
    that ``start`` begins."""

    def __init__(self, cfg: dict, tr: dict):
        self.tr = tr
        W, b, S = tr["workers"], tr["per_worker_batch"], tr["seq_len"]
        self.L = tr["log_every"]
        model = build_model(ModelConfig(**cfg["model"]))
        opt = adamw(tr["lr"], grad_clip=tr["grad_clip"])
        scfg = SolverConfig(
            m=W, T=tr["planned_steps"], eta=tr["lr"], alpha=tr["alpha"],
            aggregator="byzantine_sgd", attack=tr["attack"],
            mean_over_alive=True, guard_backend=tr["guard_backend"],
            stats_dtype=tr["stats_dtype"])
        train_step = build_train_step(model, opt, scfg)
        vocab = cfg["model"]["vocab_size"]

        def chunk(state, idx, data_seed, rank, loop_key):
            stream = SyntheticTokens(vocab_size=vocab, seq_len=S, seed=data_seed)

            def body(st, i):
                batch = make_worker_batch(stream, W, b, i)
                return train_step(st, batch, rank, jax.random.fold_in(loop_key, i))

            return jax.lax.scan(body, state, idx)

        self._chunk = jax.jit(chunk, donate_argnums=0)
        self._init = jax.jit(lambda k: init_train_state(model, opt, scfg, k))
        self._init_params = jax.jit(
            lambda k: init_train_state(model, opt, scfg, k).params)
        self.compiled = None

    def start(self, seed: int) -> None:
        """Fresh state from ``seed``; compiles the chunk on the first call."""
        if not 0 <= seed < 2 ** 32:
            raise ValueError(f"--seed must lie in [0, 2**32), got {seed}")
        # the launcher's split: one four-way split of the seed
        init_key, mask_key, _, loop_key = jax.random.split(jax.random.PRNGKey(seed), 4)
        self.args = (jnp.uint32(seed), byz_rank(mask_key, self.tr["workers"]), loop_key)
        self.init_key = init_key
        self.state = self._init(init_key)
        if self.compiled is None:
            self.compiled = self._chunk.lower(self.state, self._idx(0),
                                              *self.args).compile()
        self.next_step = 0

    def _idx(self, lo: int):
        # a host array: building it on the device would compile in the window
        return np.arange(lo, lo + self.L, dtype=np.int32)

    def run_chunk(self) -> dict:
        """One chunk; returns its per-step metrics on the host."""
        with jax.profiler.TraceAnnotation(SPAN + "dispatch"):
            self.state, ms = self.compiled(self.state, self._idx(self.next_step),
                                           *self.args)
        with jax.profiler.TraceAnnotation(SPAN + "metrics_to_host"):
            ms = jax.device_get(ms)
        self.next_step += self.L
        return ms

    def first_chunk(self) -> dict:
        """Runs the first chunk and returns what the comparison reads of
        it: per-step loss and filter decisions, and on the host, weight by
        weight, AdamW's first moment and the weights after it."""
        warm = self.run_chunk()
        self.t_warm = time.perf_counter()
        return {
            "steps": {"loss_good": [float(v) for v in warm["loss_good_workers"]],
                      **{k: [int(v) for v in warm[k]] for k in compare.FILTER_KEYS}},
            "m": _host_leaves(self.state.opt_state["m"]),
            "params": _host_leaves(self.state.params),
        }

    def weight_change(self, readings: dict) -> None:
        """Replaces the weights in ``readings`` by their change over the
        first chunk.  The weights it started from are made again by the
        same init program from the same key, once the program's state is
        freed, so that no copy of them is held through set-up."""
        params0 = _host_leaves(self._init_params(self.init_key))
        readings["dx"] = {k: np.asarray(p, np.float32) - np.asarray(params0[k], np.float32)
                          for k, p in readings.pop("params").items()}


def _host_leaves(tree) -> dict:
    """{key path: host array} of a tree's leaves."""
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(p): np.asarray(v) for p, v in flat}


def failed_worker_steps(ms: dict, workers: int) -> int:
    """Worker-steps that failed: an honest row filtered, a Byzantine row
    in the update, or every row of a step whose loss is not finite."""
    n = 0
    for j in range(len(ms["n_alive"])):
        if not (math.isfinite(ms["loss_all_workers"][j])
                and math.isfinite(ms["loss_good_workers"][j])):
            n += workers
        else:
            n += min(workers, int(ms["good_filtered"][j]) + int(ms["byz_alive"][j]))
    return n


def peak_bytes(device) -> int | None:
    """The device allocator's ``peak_bytes_in_use``: the arrays (weights,
    optimizer and guard state, batches) at their most; None where the
    device reports none.  The run's log gives ``peak_bytes_reserved``, the
    runtime's region for loaded programs, beside it."""
    stats = device.memory_stats() or {}
    return int(stats["peak_bytes_in_use"]) if "peak_bytes_in_use" in stats else None


def run_cell(cell: str, seed: int, seconds: float, traced: bool,
             t_start: float, bench: dict, log=print) -> dict:
    """One run of ``cell``; returns the result line's object."""
    clock = CompileClock()
    wl = registry.workload(cell)
    cfg = registry.config(wl["config"])
    tr = registry.traffic(wl["traffic"])
    W, L = tr["workers"], tr["log_every"]
    tokens_per_step = W * tr["per_worker_batch"] * tr["seq_len"]
    dev = jax.devices()[0]

    prog = Program(cfg, tr)
    prog.start(seed)
    mem = prog.compiled.memory_analysis()
    log(f"chunk program: compile_s={clock.seconds:.3f} memory_analysis="
        f"args {getattr(mem, 'argument_size_in_bytes', None)} "
        f"out {getattr(mem, 'output_size_in_bytes', None)} "
        f"alias {getattr(mem, 'alias_size_in_bytes', None)} "
        f"temp {getattr(mem, 'temp_size_in_bytes', None)}")
    t0 = time.perf_counter()
    readings = prog.first_chunk()
    setup_s = prog.t_warm - t_start
    log(f"warm chunk: {prog.t_warm - t0:.4f} s, setup_s={setup_s:.4f}, "
        f"compile_s={clock.seconds:.3f} ({clock.count} compiles)")

    # ---- the window ---------------------------------------------------
    compiles0 = clock.count
    tmp = tempfile.TemporaryDirectory() if traced else None
    if traced:
        jax.profiler.start_trace(tmp.name)
    steps = 0
    chunk_s, chunk_failed = [], []
    with jax.profiler.TraceAnnotation(SPAN + "window"):
        t0 = time.perf_counter()
        while True:
            tc = time.perf_counter()
            ms = prog.run_chunk()
            now = time.perf_counter()
            chunk_s.append(now - tc)
            steps += L
            chunk_failed.append(failed_worker_steps(ms, W))
            if now - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    if traced:
        jax.profiler.stop_trace()
    peak = peak_bytes(dev)
    log(f"window: {len(chunk_s)} chunks, {steps} steps in {window_s:.4f} s; "
        f"chunk_s={[round(c, 4) for c in chunk_s]}; failed worker-steps by "
        f"chunk={chunk_failed}; compiles in window: "
        f"{clock.count - compiles0}; peak_bytes_in_use={peak}; "
        f"memory_stats={dev.memory_stats()}")
    scopes = trace_lib.scope_map(prog.compiled.as_text()) if traced else {}
    del prog.state, ms
    prog.weight_change(readings)
    del prog

    record = SimpleNamespace(
        steps=steps, tokens=steps * tokens_per_step, window_s=window_s,
        chips=1, peak=peak_for(dev.device_kind) if dev.platform == "tpu" else None,
        flops_per_token=registry.reference_family(cfg["reference"]).flops_per_token(
            cfg["model"], tr["seq_len"]),
        trace=None, lo=None, hi=None)
    result = {"correct": False, "attempted": steps * W, "failed": sum(chunk_failed),
              "metrics": {}, "device": {
                  "platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak}}
    if traced:
        tr_data = trace_lib.load(trace_lib.find_xplane(tmp.name), scopes)
        tmp.cleanup()
        bounds = trace_lib.span_bounds(tr_data, SPAN + "window")
        ops = tr_data.devices[0] if tr_data.devices else []
        record.trace, record.ops = tr_data, ops
        record.lo, record.hi = bounds if bounds else (math.nan, math.nan)
        busy = (sum(trace_lib.busy_ns(o, *bounds) for o in tr_data.devices)
                / max(len(tr_data.devices), 1) * 1e-9) if bounds else 0.0
        result["device"].update(busy_s=busy, window_s=window_s)
        if bounds:
            result["breakdown"] = {
                "device_ops": trace_lib.top_ops(ops, *bounds),
                "idle_gaps": trace_lib.idle_gaps(tr_data, ops, *bounds, SPAN)}
        entries = registry.metrics_of(bench, cell, "per_layer")
    else:
        entries = registry.metrics_of(bench, cell, "end_to_end")
        record.values = {
            "tokens_per_s": record.tokens / window_s,
            "peak_hbm_gib": peak / 2 ** 30 if peak is not None else None,
            "setup_s": setup_s}
    for e in entries:
        value = (registry.metric(e["name"]).read(record) if traced
                 else record.values.get(e["name"]))
        if value is not None:
            result["metrics"][e["name"]] = {"value": value, "unit": e["unit"]}

    # ---- correctness: the first chunk against the plain reference -------
    t0 = time.perf_counter()
    ref = reference_run(registry.reference_family(cfg["reference"]), cfg["model"],
                        tr, seed, L, worker_batch=cfg.get("reference_worker_batch"))
    g = compare.gaps(readings, ref)
    values = compare.numbers(readings, ref, g)
    log(f"reference: {time.perf_counter() - t0:.3f} s; program steps "
        f"{readings['steps']}; reference steps {ref['steps']}")
    log(f"numbers: {values}; worst weights: {compare.worst_leaves(g)}")
    result["correct"], result["checks"] = compare.check(values, wl["limits"])
    return result
