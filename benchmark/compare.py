"""The comparison that decides ``correct`` for a training cell.

Both sides hand in the same readings (``losses`` per step, ``grad_norms`` of
the first step's gradient per leaf, ``delta_norms`` of the parameters' change
after the followed steps per leaf); this file turns them into the numbers
that are compared, each against a limit of its own from
``limits/<workload>.json``.

Norms are compared by the worst leaf: the gap between the program's norm and
the reference's (not the norm of a difference), over the reference's norm of
that leaf or of the median leaf, whichever is larger, since some gradients
are all but zero. Leaves whose reference gradient is under a thousandth of
the median leaf's move under Adam by round-off alone and are left out of the
change.
"""

from __future__ import annotations

import json
import statistics
import sys


def _leaf_gaps(prog: dict, ref: dict, keys) -> dict:
    floor = statistics.median(ref[k] for k in ref)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], floor) for k in keys}


def _global(norms: dict, keys) -> float:
    return sum(norms[k] ** 2 for k in keys) ** 0.5


def training_gaps(prog: dict, ref: dict) -> dict:
    """{name: value} of every number compared, lower is closer."""
    gaps = {}
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        gaps[f"loss_gap_step{i}"] = abs(lp - lr) / abs(lr)
    leaves = sorted(ref["grad_norms"])
    if sorted(prog["grad_norms"]) != leaves:
        raise ValueError("program and reference disagree on the leaves: "
                         f"{sorted(prog['grad_norms'])} vs {leaves}")
    g_floor = 1e-3 * statistics.median(ref["grad_norms"].values())
    moved = [k for k in leaves if ref["grad_norms"][k] >= g_floor]
    for what, keys in (("grad", leaves), ("delta", moved)):
        p, r = prog[f"{what}_norms"], ref[f"{what}_norms"]
        by_leaf = _leaf_gaps(p, r, keys)
        gaps[f"{what}_norm_gap"] = max(by_leaf.values())
        # a steadier companion: the gap of the norm over all these leaves
        gaps[f"{what}_norm_gap_global"] = (
            abs(_global(p, keys) - _global(r, keys)) / _global(r, keys))
        worst = sorted(by_leaf, key=by_leaf.get, reverse=True)[:3]
        print(f"{what} norms, worst leaves: " + "; ".join(
            f"{k} program {p[k]:.6g} reference {r[k]:.6g}" for k in worst),
            file=sys.stderr)
    return gaps


def judge(gaps: dict, limits: dict):
    """(correct, compared) where compared is {name: {"value", "limit"}} for
    the names the limits file holds; a name with no limit is reported with
    ``limit`` null and does not decide."""
    compared, ok = {}, True
    for name, value in gaps.items():
        limit = (limits.get(name) or {}).get("limit")
        compared[name] = {"value": value, "limit": limit}
        if limit is not None and not (value <= limit):
            ok = False
    for name, spec in limits.items():
        if name not in gaps and spec.get("limit") is not None:
            compared[name] = {"value": None, "limit": spec["limit"]}
            ok = False        # a limit with nothing to hold is a fault
    return ok, compared


def print_compared(compared: dict, correct: bool, stream=None):
    stream = stream or sys.stderr
    for name, c in compared.items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r}",
              file=stream)
    print(f"correct: {json.dumps(bool(correct))}", file=stream, flush=True)
