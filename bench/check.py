"""What decides ``correct``: the program's readings against the float32
reference's, each number beside its limit.

- ``loss1_gap``: ``|loss - ref| / |ref|`` of the first step, from the
  same weights on both sides: the forward and the loss alone.
- ``loss_gap``: the largest such gap over the checked steps.
- ``grad_gap``: over leaves (one leaf is one tensor of one layer), the
  largest ``|‖g‖ - ‖g_ref‖| / max(‖g_ref‖, median leaf's ‖g_ref‖)`` of the
  first gradient as AdamW received it (clipped).
- ``grad_err``: over leaves, the largest ``|g - g_ref| / max(|g_ref|,
  median leaf's |g_ref|)`` of the same first gradient, element by
  element, estimated from ``weights.SKETCH`` projections of each leaf on
  seeded random sign tensors.  Norms of leaves and a mean loss average
  rounding away; this number sees it.
- ``change_gap``: as ``grad_gap``, of each leaf's change after the
  checked steps.  Leaves whose reference gradient is under a thousandth of the
  median leaf's move by round-off alone under Adam; they are left out.
"""
from __future__ import annotations

import json
import math
import os
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = ("loss1_gap", "loss_gap", "grad_gap", "grad_err", "change_gap")
#: a leaf whose reference gradient norm is under this share of the
#: median leaf's is left out of change_gap
TINY_GRAD = 1e-3


def load_limits(cell: str) -> dict:
    path = os.path.join(HERE, "limits", f"{cell}.json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return json.load(f)["limits"]


def _worst_leaf(prog: dict, ref: dict, keys) -> float:
    keys = list(keys)
    med = statistics.median(ref[k] for k in keys)
    worst = 0.0
    for k in keys:
        p = prog.get(k, math.nan)
        gap = abs(p - ref[k]) / max(ref[k], med, 1e-30)
        worst = max(worst, gap) if math.isfinite(gap) else math.inf
    return worst


def _worst_sketch(prog: dict, ref: dict) -> float:
    norm = {k: math.sqrt(sum(x * x for x in v)) for k, v in ref.items()}
    med = statistics.median(norm.values())
    worst = 0.0
    for k, r in ref.items():
        p = prog.get(k)
        if p is None or len(p) != len(r):
            return math.inf
        d = math.sqrt(sum((a - b) ** 2 for a, b in zip(p, r)))
        gap = d / max(norm[k], med, 1e-30)
        worst = max(worst, gap) if math.isfinite(gap) else math.inf
    return worst


def readings(prog: dict, ref: dict) -> dict:
    """The numbers compared, from two reading sets of the same form
    (``losses``, ``grad``, ``change``)."""
    n = len(ref["losses"])
    gaps = [abs(a - b) / abs(b) for a, b in zip(prog["losses"][:n],
                                                ref["losses"])]
    if len(gaps) < n or not all(map(math.isfinite, gaps)):
        gaps = [math.inf] * n
    med_g = statistics.median(ref["grad"].values())
    moving = [k for k, v in ref["grad"].items() if v >= TINY_GRAD * med_g]
    return {"loss1_gap": gaps[0], "loss_gap": max(gaps),
            "grad_gap": _worst_leaf(prog["grad"], ref["grad"], ref["grad"]),
            "grad_err": _worst_sketch(prog["grad_sketch"],
                                      ref["grad_sketch"]),
            "change_gap": _worst_leaf(prog["change"], ref["change"], moving)}


def verdict(values: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}) over the numbers that the
    cell's limits name; the others are read and not compared (see
    PERF.md).  No limits, or a number that is not finite, is not
    correct."""
    rows, ok = {}, bool(limits)
    for name in NAMES:
        if name in limits:
            v, lim = values.get(name, math.inf), limits[name]
            rows[name] = {"value": v, "limit": lim}
            ok = ok and math.isfinite(v) and v <= lim
    return ok, rows
