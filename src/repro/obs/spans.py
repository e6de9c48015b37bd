"""Profiler spans — phase attribution for XLA profiles and host traces
(DESIGN.md §12).

Two instruments with one naming convention (``<layer>/<phase>``, e.g.
``guard/stats_sweep``, ``train/chunk``, ``serve/prefill``):

* ``jax.named_scope`` *inside* traced code.  Pure HLO metadata: an op's
  ``op_name`` gains the ``<layer>/<phase>`` prefix, so a device trace
  attributes its time to a layer of the train step.  Zero ops, zero
  numerics — always on.  :data:`STEP_SCOPES` lists the train step's
  layers; each scope is read by one per-layer metric of ``perfbench/``
  (``perfbench/layers.py`` classifies the ops):

  ============================  ==============================  ==================
  scope                         covers                          metric (ms/step)
  ============================  ==============================  ==================
  ``model/loss``                one worker's loss, inside the   ``model_fwd_ms``
                                vmap of ``value_and_grad``;     (no ``transpose(``)
                                backward ops (remat's           ``model_bwd_ms``
                                recomputation too) carry        (``transpose(``)
                                ``transpose(`` in their name
  ``flat/ravel``,               the (W, d) gradient view, the   ``flat_view_ms``
  ``flat/unravel``              flat params, ξ back to a tree
  ``adversary/attack``          the simulated Byzantine         ``adversary_ms``
                                workers: honest mean, V
                                estimate, attack, its state
  ``guard/stats_sweep``         A, B, the Grams (every guard    ``guard_stats_ms``
                                backend)
  ``guard/filter``,             the filter and ξ                ``guard_scoped_ms``
  ``guard/aggregate``, ...                                      (all ``guard/``)
  ``optim/update``              clip, moments, update, the      ``optimizer_ms``
                                parameters' add
  ============================  ==============================  ==================

  Ops in none of them (XLA-made copies and slices with no ``op_name``,
  the batch synthesis, the metrics) are ``unattributed_ms``.
* :func:`trace_span` — a host-side context manager combining
  ``jax.profiler.TraceAnnotation`` (so the span also lands on the device
  profile's host track when a profiler session is active) with a
  measurement appended to an :class:`~repro.obs.events.EventLog` as a
  ``span`` event.  ``t0_ns`` is on the profiler's host clock
  (``time.time_ns()``: an xplane's event sits at its ``Task Environment``
  plane's ``profile_start_time`` plus the event's start), so the log lays
  over a device trace; ``dur_s`` comes from ``time.perf_counter``.
  These are the measured timings the roofline comparator joins against
  ``roofline/guard_cost`` predictions.
"""
from __future__ import annotations

import contextlib
import time

import jax

# the train step's layers, each the prefix of its ``jax.named_scope``s
STEP_SCOPES = ("model/", "flat/", "adversary/", "guard/", "optim/")


@contextlib.contextmanager
def trace_span(name: str, log=None, **args):
    """Measure a host-side phase; annotate it onto any active profiler
    session and (when ``log`` is given) append a ``span`` event.

    The measured duration includes device sync only if the wrapped block
    itself blocks (callers time complete units of work — a compiled call
    + ``block_until_ready``, a chunk drain — not async dispatches).
    """
    t0_ns = time.time_ns()
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        dur = time.perf_counter() - t0
        if log is not None:
            log.event("span", name=name, t0_ns=t0_ns, dur_s=dur, **args)
