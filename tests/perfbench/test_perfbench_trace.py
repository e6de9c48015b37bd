"""The trace reduction: busy union, idle share, scope attribution, idle
gaps by host span — on hand-made events and on a small trace recorded on
a TPU v5e."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import registry, trace
from perfbench.trace import Op, Span, Trace

FIXTURE = Path(__file__).parent / "fixtures" / "tiny.xplane.pb"


def _ops():
    # a loop op (0-100) spanning two body ops, one of them scoped, then a
    # lone op after a gap
    return [Op("while.1", 0, 100, "jit(f)/while"),
            Op("fusion.2", 10, 30, "jit(f)/guard/filter/dot"),
            Op("fusion.3", 50, 40, "jit(f)/mixer/dot"),
            Op("copy.4", 150, 20, "jit(f)/copy")]


def test_union_merges_overlaps_and_clips():
    assert trace.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]
    assert trace.busy_ns(_ops(), 0, 200) == 120
    assert trace.busy_ns(_ops(), 50, 160) == 60


def test_leaves_drop_the_loop_op():
    assert [o.name for o in trace.leaves(_ops())] == ["fusion.2", "fusion.3", "copy.4"]


def test_scope_attribution():
    assert trace.scoped_ns(_ops(), "guard/", 0, 200) == 30
    assert trace.scoped_ns(_ops(), "guard/", 20, 200) == 20


def test_top_ops_and_idle_gaps():
    top = trace.top_ops(_ops(), 0, 200)
    assert top[0] == ["fusion.3 jit(f)/mixer/dot", pytest.approx(40e-9)]
    assert [name for name, _ in top] == ["fusion.3 jit(f)/mixer/dot",
                                         "fusion.2 jit(f)/guard/filter/dot",
                                         "copy.4 jit(f)/copy"]
    tr = Trace(devices=[_ops()], spans=[
        Span("perfbench/window", 0, 200), Span("perfbench/metrics_to_host", 95, 60)])
    gaps = trace.idle_gaps(tr, _ops(), 0, 200, "perfbench/")
    assert gaps[0] == ["perfbench/metrics_to_host", pytest.approx(50e-9)]
    assert gaps[1] == ["perfbench/window", pytest.approx(30e-9)]


def _record(tr, ops, lo, hi, steps=2):
    return SimpleNamespace(trace=tr, ops=ops, lo=lo, hi=hi, steps=steps,
                           tokens=steps * 10, window_s=(hi - lo) * 1e-9,
                           flops_per_token=1.0, chips=1,
                           peak=SimpleNamespace(flops_bf16=1e9))


def test_metric_readers_on_hand_made_events():
    tr = Trace(devices=[_ops()], spans=[Span("perfbench/window", 0, 200)])
    rec = _record(tr, _ops(), 0, 200)
    assert registry.metric("idle_share").read(rec) == pytest.approx(40.0)
    assert registry.metric("guard_scoped_ms").read(rec) == pytest.approx(15e-6)
    assert registry.metric("mfu").read(rec) == pytest.approx(100 * 20 / 200e-9 / 1e9)
    # nothing to read: no value, never 0
    empty = _record(Trace(), [], 0, 200)
    assert registry.metric("idle_share").read(empty) is None
    assert registry.metric("guard_scoped_ms").read(empty) is None


def test_scope_map_reads_op_names():
    text = ('  %fusion.8 = bf16[4]{0} fusion(%a), kind=kLoop, calls=%f, '
            'metadata={op_name="jit(f)/guard/filter/dot" source_file="x.py"}\n'
            '  ROOT %tuple.3 = (bf16[4]) tuple(%fusion.8)\n')
    assert trace.scope_map(text) == {"fusion.8": "jit(f)/guard/filter/dot"}
    assert trace.instruction("%fusion.8 = bf16[4]{0} fusion(%a)") == "fusion.8"


def test_recorded_tpu_trace():
    """A jitted function (a scan of a matmul, then a reduction) traced for
    two calls on one TPU v5e inside a ``perfbench/window`` span, each call
    in a ``perfbench/dispatch`` and a ``perfbench/metrics_to_host`` span."""
    tr = trace.load(FIXTURE)
    assert len(tr.devices) == 1 and tr.devices[0]
    lo, hi = trace.span_bounds(tr, "perfbench/window")
    ops = tr.devices[0]
    busy = trace.busy_ns(ops, lo, hi)
    assert 0 < busy < hi - lo
    # the scan's while op spans its body's ops: only the body is a leaf
    names = {o.name for o in trace.leaves(ops)}
    assert "fusion.8" in names and not any(n.startswith("while") for n in names)
    rec = _record(tr, ops, lo, hi)
    assert 0 < registry.metric("idle_share").read(rec) < 100
    # no scope map given: nothing is attributed, and the reader reads nothing
    assert registry.metric("guard_scoped_ms").read(rec) is None
    gaps = trace.idle_gaps(tr, ops, lo, hi, "perfbench/")
    assert {g[0] for g in gaps} <= {"perfbench/window", "perfbench/dispatch",
                                    "perfbench/metrics_to_host", "idle"}
