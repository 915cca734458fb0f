"""The comparison that decides ``correct``: the program's first rounds
against the plain reference's, number by number, each against its limit.

Numbers (each a worst case; the smaller the closer):

* ``loss_gap``: over the first rounds, the largest
  ``|loss - loss_ref| / |loss_ref|`` of a round's recorded loss.
* ``moment_gap``: after round 1, over the leaves, the largest gap between
  the norm of the program's AdamW first moment and the reference's,
  ``|n - n_ref| / max(n_ref, median leaf n_ref)``. The first moment is what
  the optimizer made of the round's gradients (split-learning jobs only:
  FedAvg's clients start each round with fresh optimizer state).
* ``change_gap``: after the last of the first rounds, the same gap for the
  norm of each leaf's change from its initial value.

Each also comes as ``<number>_median``, the median over the leaves, and
``loss_gap_round1`` is round 1's alone. A cell's limits file
(``limits/<cell>.json``) names the numbers it holds.

Leaves whose first gradient in the reference is under a thousandth of the
median leaf's (a gradient that is nought to rounding) are left out of the
two leaf numbers.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

SMALL_GRAD = 1e-3


def leaf_gaps(prog: dict, ref: dict, grad1: dict) -> dict:
    """Each leaf's gap, over the leaves both name whose first gradient in
    ``grad1`` (keyed by the same paths: a moment names its parameter's
    path) is not nought to rounding."""
    floor = SMALL_GRAD * float(np.median(list(grad1.values())))
    names = [k for k in ref if grad1.get(k, floor) >= floor]
    if set(ref) != set(prog):
        missing = sorted(set(ref) ^ set(prog))[:4]
        raise ValueError(f"program and reference name different leaves: "
                         f"{missing}")
    med = float(np.median([ref[k] for k in names]))
    return {k: (abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
                if math.isfinite(prog[k]) else math.inf) for k in names}


def _worst(gaps: dict, name: str) -> dict:
    leaf = max(gaps, key=gaps.get)
    return {name: gaps[leaf], name.replace("gap", "leaf"): leaf,
            name + "_median": float(np.median(list(gaps.values())))}


def compare(prog: dict, ref: dict) -> dict:
    """``{number: value}`` from two readings of the same rounds, each
    ``{"losses", "moment1", "change", "grad1"}`` (``grad1`` read from the
    reference)."""
    grad1 = ref["grad1"]
    losses = [abs(a - b) / abs(b) if math.isfinite(a) else math.inf
              for a, b in zip(prog["losses"], ref["losses"])]
    out = {"loss_gap": max(losses), "loss_gap_round1": losses[0]}
    if ref.get("moment1") is not None:
        out.update(_worst(leaf_gaps(prog["moment1"], ref["moment1"], grad1),
                          "moment_gap"))
    out.update(_worst(leaf_gaps(prog["change"], ref["change"], grad1),
                      "change_gap"))
    return out


def limits_of(bench_dir: str, workload: str) -> dict:
    with open(os.path.join(bench_dir, "limits", f"{workload}.json")) as f:
        return json.load(f)["limits"]


def verdict(gaps: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]) over the numbers the cell's limits
    file holds (a number the run could not read counts as failed)."""
    rows = [(k, float(gaps.get(k, math.inf)), float(lim))
            for k, lim in limits.items()]
    ok = bool(rows) and all(math.isfinite(v) and v <= lim
                            for _, v, lim in rows)
    return ok, rows
