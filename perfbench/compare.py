"""The comparison that decides ``correct``: the program's first chunk of
steps against the plain reference's same steps.

Both sides give per-step losses and filter decisions, and on the host,
weight by weight, AdamW's first moment after the chunk (the clipped
filtered gradients as the optimizer got them) and the change of the
weights over the chunk.  The numbers (each compared where the cell's
``workloads/<cell>.json`` gives it a limit, and logged in every run):

* ``loss_gap``: the largest relative gap of the honest workers' mean loss
  over the chunk's steps;
* ``moment_gap``: the worst weight's gap between the norms of the first
  moment, relative to the reference's norm of that weight or of the
  median weight, whichever is larger;
* ``update_gap``: the same for the change of the weights;
* ``moment_diff`` and ``update_diff``: the worst weight's norm of the
  difference of the first moments, and of the weights' changes, relative
  as above.  The gaps of norms see a scale; these see a direction, which
  is where lower precision and altered data show;
* ``filter_gap``: steps whose filter decisions (alive, Byzantine alive,
  honest filtered) differ; exact, limit 0.

Weights whose reference moment is under a thousandth of the median
weight's move by round-off alone; they are left out of the weight numbers.
"""
from __future__ import annotations

import math

import numpy as np

FILTER_KEYS = ("n_alive", "byz_alive", "good_filtered")
NEGLIGIBLE = 1e-3


def norm(a) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(a), dtype=np.float64))))


def _gaps(prog: dict, ref: dict, keep: list[str], diff: bool) -> dict:
    ref_n = {k: norm(v) for k, v in ref.items()}
    med = float(np.median(list(ref_n.values())))
    if diff:
        return {k: norm(np.asarray(prog[k], np.float32) - ref[k]) / max(ref_n[k], med, 1e-30)
                for k in keep}
    return {k: abs(norm(prog[k]) - ref_n[k]) / max(ref_n[k], med, 1e-30) for k in keep}


def gaps(prog: dict, ref: dict) -> dict:
    """Per-weight gaps: {number: {weight: gap}} for the weight numbers."""
    ref_m = {k: norm(v) for k, v in ref["m"].items()}
    med = float(np.median(list(ref_m.values())))
    keep = [k for k, v in ref_m.items() if v >= NEGLIGIBLE * med]
    return {"moment_gap": _gaps(prog["m"], ref["m"], keep, False),
            "update_gap": _gaps(prog["dx"], ref["dx"], keep, False),
            "moment_diff": _gaps(prog["m"], ref["m"], keep, True),
            "update_diff": _gaps(prog["dx"], ref["dx"], keep, True)}


def numbers(prog: dict, ref: dict, g: dict | None = None) -> dict:
    """The compared numbers of a program (or control) run against the
    reference; both as ``reference.train.run`` returns them.  ``g``: their
    :func:`gaps`, where already at hand."""
    lp, lr = np.asarray(prog["steps"]["loss_good"]), np.asarray(ref["steps"]["loss_good"])
    filt = sum(any(prog["steps"][k][i] != ref["steps"][k][i] for k in FILTER_KEYS)
               for i in range(len(lr)))
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr)))}
    g = gaps(prog, ref) if g is None else g
    out.update({k: max(v.values()) if v else math.nan for k, v in g.items()})
    out["filter_gap"] = float(filt)
    return out


def worst_leaves(g: dict, n: int = 3) -> dict:
    """The ``n`` weights with the largest of each of :func:`gaps`, each as
    (weight, gap), for the run's log."""
    return {k: sorted(v.items(), key=lambda kv: -kv[1])[:n] for k, v in g.items()}


def check(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {value, limit}}); a number that is not finite
    fails."""
    out = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in out.values())
    return ok, out
