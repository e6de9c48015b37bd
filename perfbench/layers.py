"""The train step's layers, told apart by the named scope of each device
op (``repro.obs.STEP_SCOPES``): every leaf op of the traced window falls
in exactly one layer, by the first rule of :data:`RULES` that its scope
text (the HLO ``op_name``, from :func:`perfbench.trace.scope_map`)
matches, and in ``unattributed`` where none does.

The model's forward and backward ops share the ``model/loss`` scope; JAX
writes ``transpose(`` into the name of every backward op, the forward
that ``jax.checkpoint`` recomputes in the backward pass among them."""
from __future__ import annotations

from perfbench import trace

# (layer, does the scope text put the op in it?); the first match wins
RULES = (
    ("guard", lambda s: "guard/" in s),
    ("adversary", lambda s: "adversary/" in s),
    ("optim", lambda s: "optim/" in s),
    ("flat", lambda s: "flat/" in s),
    ("model_bwd", lambda s: "model/" in s and "transpose(" in s),
    ("model_fwd", lambda s: "model/" in s),
)
UNATTRIBUTED = "unattributed"
LAYERS = tuple(name for name, _ in RULES) + (UNATTRIBUTED,)


def layer_of(scope: str) -> str:
    for name, test in RULES:
        if test(scope):
            return name
    return UNATTRIBUTED


def ms_per_step(record, layer: str) -> float | None:
    """Device time of the window's leaf ops in ``layer``, in milliseconds
    per step; None where there is no trace or no such op."""
    if record.trace is None or not record.ops or record.steps == 0:
        return None
    ops = [o for o in trace.leaves(record.ops) if layer_of(o.scope) == layer]
    ns = sum(b - a for a, b in trace.clip(ops, record.lo, record.hi))
    if ns == 0:
        return None
    return ns * 1e-6 / record.steps
