"""The train step's layers (``perfbench/layers.py``) and the per-layer
readers built on them, on hand-made device ops."""
from types import SimpleNamespace

import pytest

from perfbench import layers, registry, trace
from perfbench.trace import Op, Trace

STEPS = 2
# (scope, ns, layer): leaf ops back to back from t=10, inside one loop op
MIX = [
    ("jit(c)/while/body/vmap(jvp(model/loss))/dot", 100, "model_fwd"),
    ("jit(c)/while/body/vmap(transpose(jvp(model/loss)))/dot", 150, "model_bwd"),
    ("jit(c)/while/body/vmap(transpose(jvp(model/loss)))/checkpoint/"
     "rematted_computation/dot", 60, "model_bwd"),
    ("jit(c)/while/body/flat/ravel/concatenate", 30, "flat"),
    ("jit(c)/while/body/flat/unravel/slice", 10, "flat"),
    ("jit(c)/while/body/adversary/attack/dot", 40, "adversary"),
    ("jit(c)/while/body/guard/stats_sweep/add", 50, "guard"),
    ("jit(c)/while/body/guard/filter/sqrt", 20, "guard"),
    ("jit(c)/while/body/optim/update/sqrt", 70, "optim"),
    ("", 80, "unattributed"),
    ("jit(c)/while/body/SyntheticTokens.sample/add", 5, "unattributed"),
]


def _ops():
    ops, t = [], 10.0
    for i, (scope, ns, _) in enumerate(MIX):
        ops.append(Op(f"op.{i}", t, ns, scope))
        t += ns
    # the loop op spans its body; a lone op follows after a gap
    return [Op("while.0", 10.0, t - 10.0, "jit(c)/while")] + ops + [
        Op("copy.99", t + 100, 25, "")]


def _record(ops, lo=0.0, hi=2000.0, steps=STEPS):
    return SimpleNamespace(trace=Trace(devices=[ops]) if ops else Trace(),
                           ops=ops, lo=lo, hi=hi, steps=steps)


def _ns(layer):
    return sum(ns for _, ns, name in MIX if name == layer)


@pytest.mark.parametrize("metric,ns", [
    ("model_fwd_ms", _ns("model_fwd")),
    ("model_bwd_ms", _ns("model_bwd")),
    ("flat_view_ms", _ns("flat")),
    ("adversary_ms", _ns("adversary")),
    ("guard_stats_ms", 50),
    ("optimizer_ms", _ns("optim")),
    ("unattributed_ms", _ns("unattributed") + 25),
])
def test_reader_on_a_known_mix(metric, ns):
    reader = registry.metric(metric)
    assert reader.read(_record(_ops())) == pytest.approx(ns * 1e-6 / STEPS)
    # nothing to read: no value, never 0
    assert reader.read(_record([])) is None
    assert reader.read(SimpleNamespace(trace=None, steps=STEPS)) is None


def test_layers_add_up_to_busy_time():
    rec = _record(_ops())
    names = ["model_fwd_ms", "model_bwd_ms", "flat_view_ms", "adversary_ms",
             "guard_scoped_ms", "optimizer_ms", "unattributed_ms"]
    total = sum(registry.metric(n).read(rec) for n in names)
    busy = trace.busy_ns(rec.ops, rec.lo, rec.hi) * 1e-6 / STEPS
    assert total == pytest.approx(busy)
    # and a window that cuts ops at both ends
    rec = _record(_ops(), lo=50.0, hi=700.0)
    total = sum(registry.metric(n).read(rec) or 0.0 for n in names)
    assert total == pytest.approx(trace.busy_ns(rec.ops, 50.0, 700.0) * 1e-6 / STEPS)


def test_each_op_falls_in_exactly_one_layer():
    assert [layers.layer_of(scope) for scope, _, _ in MIX] == [
        layer for _, _, layer in MIX]
    # an op whose scope names two layers goes to the first rule's
    both = "jit(c)/guard/stats_sweep/vmap(transpose(jvp(model/loss)))/add"
    assert layers.layer_of(both) == "guard"
    leaves = trace.leaves(_ops())
    per_layer = {layer: [o for o in leaves if layers.layer_of(o.scope) == layer]
                 for layer in layers.LAYERS}
    assert sorted(o.name for ops in per_layer.values() for o in ops) == sorted(
        o.name for o in leaves)
