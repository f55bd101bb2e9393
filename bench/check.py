"""The comparison that decides ``correct``: the numbers read from the timed
path against the plain reference's, each held to its own limit.

The set-up drives the trainer from the seed through its first chunk, by
the window's own compiled call; the reference follows the same rounds from
the same seed. Numbers (gaps relative to the reference's reading):

- ``loss0_rel``, ``grad_norm0_rel``: round 0's mean loss and mean pre-clip
  gradient norm over the workers. Every worker starts from the shared
  initial weights and no noise has been drawn yet, so program and
  reference compute the same thing and differ by rounding only.
- ``step_rel``: the mean row's change over the chunk, leaf by leaf: the
  worst leaf's gap between the program's norm and the reference's, against
  the larger of the reference's norm of that leaf and of the median leaf.
  The DP noise cancels from the mean row, so this is the clipped local
  steps alone: the gradient, the clip and gamma. Later rounds' gradients
  are taken at noised rows, so this reads the noise's effect on them too,
  at a spread the noise law fixes.
- ``row_dev_rel``: each worker's distance from the mean row after the
  chunk (the DP noise, the mix), the worst worker's gap against the larger
  of its reference reading and the median worker's.
- ``eps_rel``: the trajectory epsilon composed from the program's in-scan
  RDP ledger against the reference's ledger of the same rounds.
- ``rounds_gap``: rounds the ledger counted minus rounds run (exact).
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone and is left out of ``step_rel``
NOUGHT_LEAF = 1e-3


def _rel(p, r):
    return abs(p - r) / abs(r) if r else abs(p - r)


def _worst(p, r, keep=None):
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    if keep is not None:
        p, r = p[keep], r[keep]
    floor = np.maximum(np.abs(r), np.median(np.abs(r)))
    return float(np.max(np.abs(p - r) / floor))


def _by_shape(pairs):
    """[(shape, value)] -> {(shape, k): value}, k counting the leaves of one
    shape in order: how the program's leaves meet the reference's."""
    seen, out = {}, {}
    for shape, v in pairs:
        shape = tuple(shape)
        k = seen[shape] = seen.get(shape, -1) + 1
        out[(shape, k)] = v
    return out


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    g0 = _by_shape(ref["grad0_leaf"])
    want = _by_shape(ref["change_leaf"])
    got = _by_shape(prog["change_leaf"])
    if set(got) != set(want):
        raise ValueError(f"leaves differ: program {sorted(got)}, "
                         f"reference {sorted(want)}")
    keys = sorted(want)
    g = np.array([g0[k] for k in keys])
    moved = g >= NOUGHT_LEAF * np.median(g)
    return {
        "loss0_rel": _rel(prog["loss"][0], ref["loss"][0]),
        "grad_norm0_rel": _rel(prog["grad_norm"][0], ref["grad_norm"][0]),
        "step_rel": _worst([got[k] for k in keys], [want[k] for k in keys],
                           moved),
        "row_dev_rel": _worst(prog["row_dev"], ref["row_dev"]),
        "eps_rel": _rel(prog["eps"], ref["eps"]),
        "rounds_gap": float(abs(prog["rounds"] - ref["rounds"])),
    }


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[str], Dict[str, dict]]:
    """Every number within its limit (a NaN is a failure; a number with no
    limit fails, so a new number cannot pass unseen)."""
    ok, lines, table = True, [], {}
    for name, value in values.items():
        limit = limits.get(name)
        value = float(value)
        good = bool(limit is not None and not math.isnan(value)
                    and value <= limit)
        ok &= good
        table[name] = {"value": value, "limit": limit}
        lines.append(f"check {name} {value!r} limit {limit!r} "
                     f"{'ok' if good else 'FAIL'}")
    return ok, lines, table
