"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy time, the time of ops under a named scope,
the longest ops, and the idle gaps with the host span that was open in
each.

Read with ``jax.profiler.ProfileData`` alone.  Device planes are named
``/device:<platform>:<n>``; their ``XLA Ops`` line holds one event per op
execution, named by the op's HLO text (``%fusion.8 = ...``).  An op's scope
(``jax.named_scope``) is not in the event: it is the ``op_name`` of the
instruction's metadata in the compiled program's HLO text, which
:func:`scope_map` reads.  Host spans (``TraceAnnotation``) are events on
the host plane's threads.  Times are nanoseconds; on a TPU v5e the device
clock of the trace runs about a millisecond apart from the host's, which
moves the window's ends by that much.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path

OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


@dataclass
class Op:
    name: str           # the HLO instruction, e.g. ``fusion.8``
    start_ns: float
    dur_ns: float
    scope: str          # its metadata ``op_name``: the named-scope path


@dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float


@dataclass
class Trace:
    """One device's ops (``devices`` of them, one list each) and the host
    spans, all on the same clock."""

    devices: list[list[Op]] = field(default_factory=list)
    spans: list[Span] = field(default_factory=list)


def find_xplane(directory: str | Path) -> Path:
    paths = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return Path(paths[-1])


def scope_map(hlo_text: str) -> dict[str, str]:
    """{instruction name: metadata op_name} of a compiled program's HLO."""
    out = {}
    for line in hlo_text.splitlines():
        m, o = _INSTR.match(line), _OP_NAME.search(line)
        if m and o:
            out[m.group(1)] = o.group(1)
    return out


def instruction(event_name: str) -> str:
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def load(path: str | Path, scopes: dict[str, str] | None = None) -> Trace:
    """The trace at ``path``; ``scopes`` (from :func:`scope_map`) names
    each op's scope."""
    from jax.profiler import ProfileData

    scopes = scopes or {}

    data = ProfileData.from_file(str(path))
    trace = Trace()
    for plane in data.planes:
        if _DEVICE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        name = instruction(ev.name)
                        ops.append(Op(name, float(ev.start_ns),
                                      float(ev.duration_ns), scopes.get(name, "")))
            trace.devices.append(sorted(ops, key=lambda o: o.start_ns))
        elif plane.name.startswith("/host:") and plane.name != "/host:metadata":
            for line in plane.lines:
                trace.spans.extend(
                    Span(ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events if ev.duration_ns > 0)
    return trace


def span_bounds(trace: Trace, name: str) -> tuple[float, float] | None:
    """(start, end) of the first host span called ``name``."""
    for s in trace.spans:
        if s.name == name:
            return s.start_ns, s.start_ns + s.dur_ns
    return None


def clip(ops: list[Op], lo: float, hi: float) -> list[tuple[float, float]]:
    """Op intervals cut to [lo, hi]."""
    out = []
    for o in ops:
        a, b = max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(ops: list[Op], lo: float, hi: float) -> float:
    """Length of the union of op intervals inside [lo, hi]."""
    return sum(b - a for a, b in union(clip(ops, lo, hi)))


def leaves(ops: list[Op]) -> list[Op]:
    """Ops that contain no other op of the same device: a loop or call op
    spans its body's ops, which are counted on their own."""
    out, n = [], len(ops)
    for i, o in enumerate(ops):
        end = o.start_ns + o.dur_ns
        if i + 1 < n and ops[i + 1].start_ns < end and ops[i + 1].dur_ns < o.dur_ns:
            continue
        out.append(o)
    return out


def scoped_ns(ops: list[Op], scope: str, lo: float, hi: float) -> float:
    """Device time of leaf ops inside [lo, hi] whose scope text holds
    ``scope``."""
    return sum(b - a for a, b in clip(
        [o for o in leaves(ops) if scope in o.scope], lo, hi))


def top_ops(ops: list[Op], lo: float, hi: float, n: int = 10):
    """[(op, seconds)] of the leaf ops that took most time in [lo, hi],
    summed over executions; each op named by its instruction and scope."""
    tot: dict[str, float] = {}
    for o in leaves(ops):
        a, b = max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi)
        if b > a:
            key = f"{o.name} {o.scope}".strip()
            tot[key] = tot.get(key, 0.0) + (b - a)
    return [[k, v * 1e-9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, ops: list[Op], lo: float, hi: float,
              prefix: str, n: int = 10):
    """[(host span, seconds)] of the ``n`` longest idle gaps of the device
    in [lo, hi], each named by the innermost host span whose name starts
    with ``prefix`` and that covers the gap's midpoint (``idle`` where
    none does)."""
    busy = union(clip(ops, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [s for s in trace.spans if s.name.startswith(prefix)]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        inside = [s for s in spans if s.start_ns <= mid <= s.start_ns + s.dur_ns]
        name = min(inside, key=lambda s: s.dur_ns).name if inside else "idle"
        out.append([name, (b - a) * 1e-9])
    return out
