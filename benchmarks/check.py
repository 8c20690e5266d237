"""The comparison that decides ``correct``: the program's first three steps
(taken through the window's own call and feed) against the plain reference.

Numbers compared, each against a limit of its own from
``benchmarks/limits/<workload>.json`` (how each limit was set: PERF.md):

- ``rows_wrong``: batches the loop consumed that are not the batch the seed
  and the sampler's order define at that position. Exact: limit 0.
- ``loss_gap``: the widest relative gap of a step's loss.
- ``grad_gap``: worst leaf of |program's norm - reference's norm| of the
  first gradient, over the larger of the reference's norm of that leaf and of
  its median leaf.
- ``grad_gap_whole``: the gap of the norm of the whole first gradient (all
  leaves together): a worst leaf swings from seed to seed, the whole does
  not, and it is the number the int8 control fails.
- ``change_gap``: the worst leaf's gap of each leaf's change after the three steps,
  over the leaves whose reference gradient is at least a thousandth of the
  median leaf's (the others move by round-off alone under a sign-like
  update).

Stdlib only.
"""

from __future__ import annotations

import json
import os
import statistics

_HERE = os.path.dirname(os.path.abspath(__file__))
NEGLIGIBLE_GRADIENT = 1e-3   # of the median leaf's norm


def limits_for(workload: str) -> dict:
    with open(os.path.join(_HERE, "limits", workload + ".json")) as f:
        return json.load(f)["limits"]


def worst_leaf_gap(program: dict, reference: dict, leaves=None) -> tuple:
    """(gap, leaf) over ``leaves`` (default: all the reference has)."""
    floor = statistics.median(reference.values())
    worst, where = 0.0, ""
    for name in (leaves if leaves is not None else reference):
        if name not in program:
            return float("inf"), name
        gap = abs(program[name] - reference[name]) / max(reference[name],
                                                         floor)
        if not gap <= worst:      # a NaN gap is the worst there is
            worst, where = gap, name
    return worst, where


def whole_norm_gap(program: dict, reference: dict, leaves=None) -> float:
    """Gap of the norm over all the leaves together."""
    names = list(leaves if leaves is not None else reference)
    whole = sum(reference[n] ** 2 for n in names) ** 0.5
    mine = sum(program.get(n, float("inf")) ** 2 for n in names) ** 0.5
    return abs(mine - whole) / whole


def moving_leaves(reference_grad_norms: dict) -> list:
    floor = NEGLIGIBLE_GRADIENT * statistics.median(
        reference_grad_norms.values())
    return [k for k, v in reference_grad_norms.items() if v >= floor]


def compare(program: dict, reference: dict, rows_wrong: int) -> dict:
    """name -> {"value", "where"}; ``program`` and ``reference`` hold
    ``losses``, ``grad_norms`` and ``change_norms``."""
    loss_gap = max(
        (abs(p - r) / abs(r) for p, r in zip(program["losses"],
                                             reference["losses"])),
        default=float("inf"))
    if len(program["losses"]) != len(reference["losses"]):
        loss_gap = float("inf")
    grad_gap, grad_leaf = worst_leaf_gap(program["grad_norms"],
                                         reference["grad_norms"])
    change_gap, change_leaf = worst_leaf_gap(
        program["change_norms"], reference["change_norms"],
        moving_leaves(reference["grad_norms"]))
    moving = moving_leaves(reference["grad_norms"])
    return {
        "grad_gap_whole": {"value": whole_norm_gap(
            program["grad_norms"], reference["grad_norms"]), "where": ""},
        "rows_wrong": {"value": rows_wrong, "where": ""},
        "loss_gap": {"value": loss_gap, "where": ""},
        "grad_gap": {"value": grad_gap, "where": grad_leaf},
        "change_gap": {"value": change_gap, "where": change_leaf},
    }


def verdict(compared: dict, limits: dict) -> tuple:
    """(correct, {name: [value, limit]}): every compared number has to be
    there, be a number and lie at or under its limit."""
    table, correct = {}, True
    for name, limit in limits.items():
        value = compared.get(name, {}).get("value")
        table[name] = [value, limit]
        if value is None or not value <= limit:
            correct = False
    return correct, table
