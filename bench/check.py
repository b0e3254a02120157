"""The comparison that decides ``correct``.

The program's first three training steps (``program.check_steps``) against
the float32 reference's (``reference.train``), as three numbers:

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_gap``: over the leaves, the largest gap between the norms of the
  first gradient as the optimizers get it, against the reference's norm of
  that leaf or of the median leaf, whichever is larger;
- ``change_gap``: the same for the norms of each leaf's change over the
  three steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (below that a leaf moves under Adam by
  round-off alone);

and ``routing_overflow``, the keys the program's fixed-capacity routing
dropped in those steps (exact: 0). Each is held to the cell's limit.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "routing_overflow")
MOVES_BELOW = 1e-3  # of the median leaf's reference gradient


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              leaves: List[str]) -> float:
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: program {sorted(set(prog) - set(ref))}"
                         f" reference {sorted(set(ref) - set(prog))}")
    med = float(np.median([ref[k] for k in leaves]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)


def numbers(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    steps = len(ref["losses"])
    lp, lr = prog["losses"][:steps], ref["losses"]
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(lp, lr))
    if len(lp) < steps or not np.all(np.isfinite(lp)):
        loss_gap = float("inf")
    g = ref["grad_norms"]
    med = float(np.median(list(g.values())))
    moving = [k for k, v in g.items() if v >= MOVES_BELOW * med]
    return {
        "loss_gap": loss_gap,
        "grad_gap": _leaf_gap(prog["grad_norms"], g, sorted(g)),
        "change_gap": _leaf_gap(prog["change_norms"], ref["change_norms"],
                                moving),
        "routing_overflow": float(prog.get("overflow", 0)),
    }


def verdict(values: Dict[str, float], limits: Dict[str, Optional[float]],
            not_compared: Sequence[str] = ()
            ) -> Tuple[bool, Dict[str, Dict[str, Optional[float]]]]:
    """``correct`` and each number beside its limit. A number with no limit
    (not yet set for this cell) fails, as does one that is not finite,
    unless the cell lists it as ``not_compared`` (no control or fault
    reads far enough above the program to set a limit)."""
    out = {}
    ok = True
    for k in NUMBERS:
        v, lim = values[k], limits.get(k)
        out[k] = {"value": v, "limit": lim}
        if k in not_compared:
            continue
        if lim is None or not np.isfinite(v) or v > lim:
            ok = False
    return ok, out


def lines(checks: Dict[str, Dict[str, Optional[float]]]) -> List[str]:
    return [f"check {k}: {c['value']!r} (limit {c['limit']!r})"
            for k, c in checks.items()]
