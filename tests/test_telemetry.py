"""Guard flight recorder (DESIGN.md §12): the off-state contract and the
in-trace forensics.

The acceptance-critical property is that telemetry is *free when off*:
``telemetry=None`` and ``TelemetryConfig(enabled=False)`` must produce the
same jaxpr (no telemetry ops traced at all) and bit-identical results, and
arming the recorder must not change a single filter decision — the frames
are a read-only tap on the guard's own diagnostics.  The rest pins the
recorder's data path: the packed single-lane ring buffer, the
first-filter/survival summaries, the campaign timeline export, the
trainer's uniform metrics schema, and the JSONL/chrome-trace writers.
"""
import importlib.util
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.solver import SolverConfig, run_sgd
from repro.data.problems import make_quadratic_problem
from repro.obs import (
    EventLog,
    FRAME_SCHEMA,
    PER_WORKER_KEYS,
    SCALAR_KEYS,
    Telemetry,
    TelemetryConfig,
    empty_frame,
    provenance_meta,
    ring_init,
    ring_push,
    ring_read,
    spans_by_name,
    telemetry_on,
    trace_span,
    write_chrome_trace,
)
from repro.scenarios import (
    expand_grid,
    run_campaign,
    scenario_adaptive,
    scenario_static,
)
from repro.scenarios.report import (
    _survival_curve,
    campaign_trace_events,
    filter_timelines,
    summarize_campaign,
)


@pytest.fixture(scope="module")
def quad():
    return make_quadratic_problem(d=8, sigma=1.0, L=8.0, V=1.0, seed=3)


def _cfg(**kw):
    base = dict(m=8, T=30, eta=0.05, alpha=0.25,
                aggregator="byzantine_sgd", attack="sign_flip")
    base.update(kw)
    return SolverConfig(**base)


# ---------------------------------------------------------------------------
# ring buffer
# ---------------------------------------------------------------------------

class TestRing:
    def _frame(self, m, step):
        frame = empty_frame(m)
        frame["alive"] = jnp.arange(m, dtype=jnp.float32)
        frame["step"] = jnp.asarray(float(step), jnp.float32)
        frame["n_alive"] = jnp.asarray(m - step, jnp.float32)
        return frame

    def test_packed_width(self):
        ring = ring_init(m=5, ring_size=4)
        assert ring.lanes.shape == (4, len(PER_WORKER_KEYS) * 5
                                    + len(SCALAR_KEYS))
        assert ring.m == 5

    def test_push_read_round_trip(self):
        ring = ring_init(m=3, ring_size=8)
        for s in range(1, 4):
            ring = ring_push(ring, self._frame(3, s))
        frames = ring_read(ring)
        assert len(frames) == 3
        assert set(frames[0]) == set(FRAME_SCHEMA)
        assert [float(f["step"]) for f in frames] == [1.0, 2.0, 3.0]
        np.testing.assert_array_equal(frames[0]["alive"], [0.0, 1.0, 2.0])
        assert np.isnan(frames[0]["thr_a"])   # NaN sentinel preserved

    def test_wrap_keeps_last_ring_size_in_order(self):
        ring = ring_init(m=2, ring_size=4)
        for s in range(1, 11):                 # 10 pushes into 4 slots
            ring = ring_push(ring, self._frame(2, s))
        frames = ring_read(ring)
        assert int(ring.head) == 10
        assert [float(f["step"]) for f in frames] == [7.0, 8.0, 9.0, 10.0]

    def test_config_gate(self):
        assert not telemetry_on(None)
        assert not telemetry_on(TelemetryConfig(enabled=False))
        assert telemetry_on(TelemetryConfig())


# ---------------------------------------------------------------------------
# off-state: trace-identical and bit-identical (acceptance criterion)
# ---------------------------------------------------------------------------

class TestOffStateEquivalence:
    @pytest.mark.parametrize("backend", ["dense", "fused"])
    def test_disabled_jaxpr_identical_to_none(self, quad, backend):
        cfg = _cfg(guard_backend=backend)
        key = jax.random.PRNGKey(0)
        j_none = jax.make_jaxpr(
            lambda k: run_sgd(quad, cfg, k, telemetry=None))(key)
        j_off = jax.make_jaxpr(
            lambda k: run_sgd(quad, cfg, k,
                              telemetry=TelemetryConfig(enabled=False)))(key)
        assert str(j_none) == str(j_off)

    def test_disabled_results_bit_identical(self, quad):
        cfg = _cfg()
        key = jax.random.PRNGKey(7)
        a = run_sgd(quad, cfg, key)
        b = run_sgd(quad, cfg, key, telemetry=TelemetryConfig(enabled=False))
        assert a.telemetry is None and b.telemetry is None
        np.testing.assert_array_equal(np.asarray(a.x_final),
                                      np.asarray(b.x_final))
        np.testing.assert_array_equal(np.asarray(a.gaps), np.asarray(b.gaps))

    @pytest.mark.parametrize("backend",
                             ["dense", "fused", "dp_exact", "dp_sketch"])
    def test_enabled_leaves_filter_decisions_unchanged(self, quad, backend):
        cfg = _cfg(guard_backend=backend,
                   guard_opts=(("sketch_dim", 8),))
        key = jax.random.PRNGKey(5)
        off = run_sgd(quad, cfg, key)
        on = run_sgd(quad, cfg, key, telemetry=TelemetryConfig(ring_size=16))
        np.testing.assert_array_equal(np.asarray(off.n_alive),
                                      np.asarray(on.n_alive))
        np.testing.assert_array_equal(np.asarray(off.final_alive),
                                      np.asarray(on.final_alive))
        np.testing.assert_array_equal(np.asarray(off.x_final),
                                      np.asarray(on.x_final))

    def test_enabled_baseline_aggregator_unchanged(self, quad):
        cfg = _cfg(aggregator="krum")
        key = jax.random.PRNGKey(5)
        off = run_sgd(quad, cfg, key)
        on = run_sgd(quad, cfg, key, telemetry=TelemetryConfig())
        np.testing.assert_array_equal(np.asarray(off.x_final),
                                      np.asarray(on.x_final))


# ---------------------------------------------------------------------------
# what the armed recorder captures
# ---------------------------------------------------------------------------

class TestRecorder:
    def test_guard_run_frames_and_summaries(self, quad):
        cfg = _cfg()
        res = run_sgd(quad, cfg, jax.random.PRNGKey(2),
                      telemetry=TelemetryConfig(ring_size=16))
        tel = res.telemetry
        assert isinstance(tel, Telemetry)
        assert tel.byz_alive.shape == (cfg.T,)
        frames = ring_read(tel.ring)
        assert len(frames) == 16                       # T=30 wrapped the ring
        assert float(frames[-1]["step"]) == cfg.T
        last = frames[-1]
        assert np.isfinite(last["thr_a"]) and np.isfinite(last["thr_b"])
        assert np.isfinite(last["dev_a"]).all()
        assert float(last["n_alive"]) == float(res.n_alive[-1])
        np.testing.assert_array_equal(
            last["alive"], np.asarray(res.final_alive, np.float32))
        assert np.isfinite(last["xi_norm"])

        # sign-flip at α=.25 gets every byz worker filtered; ffs marks the
        # byz workers with a positive step and the good workers with -1
        ffs = np.asarray(tel.first_filter_step)
        byz = np.asarray(res.byz_mask)
        assert (ffs[byz] > 0).all()
        assert (ffs[~byz] == -1).all()
        assert int(tel.byz_alive[-1]) == 0

    def test_baseline_frames_nan_thresholds(self, quad):
        res = run_sgd(quad, _cfg(aggregator="krum"), jax.random.PRNGKey(2),
                      telemetry=TelemetryConfig(ring_size=8))
        last = ring_read(res.telemetry.ring)[-1]
        assert np.isnan(last["thr_a"]) and np.isnan(last["dev_a"]).all()
        assert np.isfinite(last["n_alive"])

    def test_dp_backend_reports_v_est(self, quad):
        res = run_sgd(quad, _cfg(guard_backend="dp_exact"),
                      jax.random.PRNGKey(2),
                      telemetry=TelemetryConfig(ring_size=8))
        assert np.isfinite(float(ring_read(res.telemetry.ring)[-1]["v_est"]))


# ---------------------------------------------------------------------------
# campaign plumbing + report sections
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_campaign(quad):
    grid = expand_grid(
        [("static_sign_flip", scenario_static("sign_flip")),
         ("adaptive_inner_product",
          scenario_adaptive("inner_product", adapt_rate=0.5))],
        alphas=[0.25], seeds=[0, 1],
    )
    return run_campaign(quad, _cfg(T=40), grid, ["byzantine_sgd"],
                        telemetry=TelemetryConfig(ring_size=8)), grid


class TestCampaign:
    def test_runstats_telemetry_none_when_off(self, quad):
        grid = expand_grid([("s", scenario_static("sign_flip"))],
                           alphas=[0.25], seeds=[0])
        result = run_campaign(quad, _cfg(), grid, ["byzantine_sgd"])
        (stats,) = result.stats.values()
        assert stats.telemetry is None

    def test_runstats_telemetry_block(self, traced_campaign):
        result, grid = traced_campaign
        (stats,) = result.stats.values()
        tel = stats.telemetry
        assert set(tel) >= {"ring", "first_filter_step", "byz_alive",
                            "byz_mask"}
        n = grid.n_runs
        assert tel["first_filter_step"].shape == (n, 8)
        assert tel["byz_alive"].shape == (n, 40)
        assert tel["ring"].lanes.shape[0] == n      # vmapped ring

    def test_filter_timelines_rows(self, traced_campaign):
        result, grid = traced_campaign
        rows = filter_timelines(result)
        assert len(rows) == 2                       # one per scenario×alpha
        row = {r["scenario"]: r for r in rows}["static_sign_flip"]
        assert row["n_seeds"] == 2
        assert row["n_byz_caught"] == row["n_byz_workers"] > 0
        assert row["first_filter_byz_med"] > 0
        curve = row["byz_survival"]
        assert curve[0][0] == 1 and curve[-1][0] == 40
        assert curve[-1][1] == 0                    # all byz gone by T

    def test_summarize_campaign_attaches_timelines(self, traced_campaign,
                                                   quad):
        result, _ = traced_campaign
        record = summarize_campaign(result, quad, _cfg(T=40))
        assert "filter_timelines" in record

    def test_campaign_trace_events(self, traced_campaign):
        result, _ = traced_campaign
        log = EventLog(tool="test")
        n = campaign_trace_events(
            result, log,
            select=lambda e: e["scenario"] == "adaptive_inner_product")
        assert n == 2                               # 2 seeds selected
        kinds = {e["type"] for e in log.events}
        assert kinds == {"guard_step", "timeline"}
        steps = [e for e in log.events if e["type"] == "guard_step"]
        assert len(steps) == 2 * 8                  # ring_size per cell
        tl = next(e for e in log.events if e["type"] == "timeline")
        assert tl["byz_survival"][0][0] == 1

    def test_survival_curve_compression(self):
        series = np.array([4, 4, 4, 2, 2, 0, 0, 0])
        assert _survival_curve(series) == [[1, 4], [4, 2], [6, 0], [8, 0]]
        dense = np.arange(200, 0, -1)
        assert len(_survival_curve(dense, max_points=64)) <= 64


# ---------------------------------------------------------------------------
# event log + chrome trace + spans
# ---------------------------------------------------------------------------

class TestEventLog:
    def test_jsonl_round_trip(self, tmp_path):
        log = EventLog(tool="test", scenario="s")
        log.event("counter", name="serve/throughput", tokens_per_s=12.5)
        log.guard_step({"step": 1.0, "n_alive": jnp.asarray(8.0),
                        "dev_a": np.array([0.1, np.nan])}, run="r")
        log.add_meta(telemetry_overhead_frac=0.017)
        path = tmp_path / "t.jsonl"
        log.write_jsonl(str(path))
        meta, events = EventLog.read_jsonl(str(path))
        assert meta["tool"] == "test"
        assert meta["telemetry_overhead_frac"] == 0.017
        assert {"commit", "jax_version", "device_kind"} <= set(meta)
        assert len(events) == 2
        assert events[1]["dev_a"] == [0.1, None]    # NaN → null sentinel

    def test_chrome_trace_projection(self, tmp_path):
        log = EventLog(tool="test")
        with trace_span("train/chunk", log=log, lo=0, hi=4):
            pass
        with trace_span("train/chunk", log=log, lo=4, hi=8):
            pass
        log.guard_step({"step": 3.0, "n_alive": 7.0, "xi_norm": 0.5},
                       run="r")
        out = tmp_path / "t.json"
        log.write_chrome_trace(str(out))
        trace = json.loads(out.read_text())
        phases = {ev["ph"] for ev in trace["traceEvents"]}
        assert {"X", "C"} <= phases
        counter = next(ev for ev in trace["traceEvents"]
                       if ev["ph"] == "C" and "n_alive" in ev["name"])
        assert counter["ts"] == 3
        # slices are timed in µs from the first span's t0_ns, which the
        # metadata keeps: the export lays over the host clock exactly
        first, second = log.events[:2]
        slices = [ev for ev in trace["traceEvents"] if ev["ph"] == "X"]
        assert trace["metadata"]["span_origin_ns"] == first["t0_ns"]
        assert slices[0]["ts"] == 0
        assert slices[1]["ts"] == (second["t0_ns"] - first["t0_ns"]) * 1e-3
        assert slices[1]["args"] == {"lo": 4, "hi": 8}

    def test_trace_span_t0_on_the_profilers_host_clock(self, tmp_path):
        """A span's logged ``t0_ns`` is where the profiler puts its
        annotation: the xplane's ``profile_start_time`` plus the event's
        start, both on ``time.time_ns()``'s clock."""
        from jax.profiler import ProfileData

        log = EventLog(tool="test")
        before = time.time_ns()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with trace_span("test/clock", log=log):
                pass
        finally:
            jax.profiler.stop_trace()
        span = log.events[0]
        assert before <= span["t0_ns"] <= time.time_ns()
        data = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
        start_ns = next(dict(p.stats)["profile_start_time"]
                        for p in data.planes if p.name == "Task Environment")
        ann = next(e for p in data.planes for line in p.lines
                   for e in line.events if e.name == "test/clock")
        assert abs(start_ns + ann.start_ns - span["t0_ns"]) < 1e6   # 1 ms

    def test_trace_span_without_log(self):
        with trace_span("guard/filter"):
            x = jnp.ones(3).sum()
        assert float(x) == 3.0

    def test_spans_by_name(self):
        log = EventLog(tool="test")
        for _ in range(3):
            with trace_span("train/step", log=log):
                pass
        rec = spans_by_name(log.events)["train/step"]
        assert rec["count"] == 3
        assert rec["total_s"] >= 0.0

    def test_provenance_meta_keys(self):
        meta = provenance_meta()
        assert {"commit", "timestamp", "jax_version", "jaxlib_version",
                "backend", "device_kind", "n_devices"} <= set(meta)


# ---------------------------------------------------------------------------
# trainer: uniform metrics schema + tel/ channel
# ---------------------------------------------------------------------------

class TestTrainerMetrics:
    @pytest.fixture(scope="class")
    def lm(self):
        from repro.configs import get_config
        from repro.models import build_model
        cfg = get_config("internlm2-1.8b").reduced(max_d_model=32)
        return cfg, build_model(cfg)

    def _run_step(self, lm, scfg, telemetry=None):
        from repro.distributed.trainer import (
            build_train_step, init_train_state, rank_from_mask,
        )
        from repro.optim import adamw
        from repro.data.synthetic import SyntheticTokens, make_worker_batch
        cfg, model = lm
        rng = jax.random.PRNGKey(0)
        opt = adamw(1e-3)
        ts = jax.jit(build_train_step(model, opt, scfg, telemetry=telemetry))
        state = init_train_state(model, opt, scfg, rng)
        stream = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=16)
        batch = make_worker_batch(stream, scfg.m, 1, jnp.asarray(0))
        rank = rank_from_mask(jnp.arange(scfg.m) < scfg.n_byzantine)
        return ts(state, batch, rank, jax.random.fold_in(rng, 0))

    @pytest.mark.parametrize("agg,backend", [
        ("byzantine_sgd", "dp_exact"), ("mean", "dense"),
    ])
    def test_v_est_key_uniform_across_aggregators(self, lm, agg, backend):
        scfg = SolverConfig(m=4, T=4, eta=1e-3, alpha=0.25, aggregator=agg,
                            attack="sign_flip", guard_backend=backend)
        _, metrics = self._run_step(lm, scfg)
        assert "v_est" in metrics
        v = float(metrics["v_est"])
        if agg == "byzantine_sgd":
            assert np.isfinite(v)                   # dp auto-V estimate
        else:
            assert np.isnan(v)                      # NaN sentinel, not absent

    def test_tel_metrics_present_only_when_armed(self, lm):
        scfg = SolverConfig(m=4, T=4, eta=1e-3, alpha=0.25,
                            aggregator="byzantine_sgd", attack="sign_flip",
                            guard_backend="dp_exact")
        _, off = self._run_step(lm, scfg)
        assert not any(k.startswith("tel/") for k in off)
        _, on = self._run_step(lm, scfg, telemetry=TelemetryConfig())
        for key in FRAME_SCHEMA:
            assert f"tel/{key}" in on
        assert on["tel/alive"].shape == (4,)
        assert float(on["tel/step"]) == 1.0
        # armed telemetry must not perturb the training metrics
        np.testing.assert_array_equal(np.asarray(off["loss_good_workers"]),
                                      np.asarray(on["loss_good_workers"]))
        np.testing.assert_array_equal(np.asarray(off["n_alive"]),
                                      np.asarray(on["n_alive"]))


# ---------------------------------------------------------------------------
# renderer + benchmark provenance
# ---------------------------------------------------------------------------

def _load_render_trace():
    path = (pathlib.Path(__file__).resolve().parents[1]
            / "scripts" / "render_trace.py")
    spec = importlib.util.spec_from_file_location("render_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestRenderer:
    def test_render_synthetic_trace(self):
        rt = _load_render_trace()
        log = EventLog(tool="test", telemetry_overhead_frac=0.02)
        with trace_span("guard/filter", log=log):
            pass
        log.event("roofline", backend="dense", m=8, d=8,
                  measured_step_us=10.0, modeled_step_us=2.0,
                  measured_over_model=5.0)
        log.guard_step({"step": 2.0, "n_alive": 6.0, "xi_norm": 0.4,
                        "thr_a": 9.0, "thr_b": 4.0,
                        "dev_a": [0.1, 8.0], "dist_b": [0.2, 5.0],
                        "alive": [1.0, 0.0]}, run="s/a0.25/agg/s0")
        log.event("timeline", run="s/a0.25/agg/s0",
                  first_filter_step=[-1, 2], byz_mask=[False, True],
                  byz_survival=[[1, 1], [2, 0], [4, 0]])
        text = rt.render(log.meta, log.events)
        assert "telemetry_overhead_frac" in text
        assert "first-filter (byz): [2]" in text
        assert "guard/filter" in text
        assert "5.0x" in text

    def test_sparkline_and_survival_expansion(self):
        rt = _load_render_trace()
        vals = rt._survival_values(
            {"byz_survival": [[1, 2], [3, 0], [5, 0]]}, [])
        assert vals == [2.0, 2.0, 0.0, 0.0, 0.0]
        assert len(rt._sparkline([0.0, 1.0, 2.0], width=48)) == 3
        assert rt._sparkline([2.0, 0.0])[0] == "█"


class TestBenchProvenance:
    def test_write_json_injects_meta(self, tmp_path):
        import benchmarks.common as common
        path = tmp_path / "BENCH_x.json"
        common.write_json(str(path), {"result_us": 1.0})
        rec = json.loads(path.read_text())
        assert rec["result_us"] == 1.0
        assert {"commit", "jax_version", "device_kind"} <= set(rec["meta"])

    def test_write_json_keeps_caller_meta(self, tmp_path):
        import benchmarks.common as common
        path = tmp_path / "BENCH_y.json"
        common.write_json(str(path), {"meta": {"custom": 1}})
        assert json.loads(path.read_text())["meta"] == {"custom": 1}
