"""The numbers that decide ``correct``, from the program's and the
reference's readings of the same first steps.

- ``loss_gap``: the largest relative gap between the program's and the
  reference's loss over the compared steps;
- ``grad_gap``: over every layer of every leaf, the largest gap between
  the norms of the first step's gradient (the program's worked out from
  Adam's first moment after one step), over the reference's norm of that
  layer or of the median layer, whichever is larger;
- ``update_gap``: the same for the parameters' change over the compared
  steps, leaving out layers whose reference gradient is under a thousandth
  of the median layer's: those move by round-off alone.

Each is a gap of norms, not the norm of a difference: rounding in bf16
moves single elements, and a fault moves a whole layer.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

#: a layer whose reference gradient norm is under this share of the median
#: layer's is left out of ``update_gap``
ROUNDOFF_SHARE = 1e-3


def _flat(norms: Dict[str, np.ndarray], keep: Optional[Dict] = None):
    names, vals = [], []
    for k in sorted(norms):
        v = np.asarray(norms[k], np.float64).ravel()
        mask = np.ones(v.shape, bool) if keep is None else keep[k]
        for i in np.nonzero(mask)[0]:
            names.append(f"{k}[{i}]")
            vals.append(v[i])
    return names, np.asarray(vals)


def worst_layer(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                keep: Optional[Dict] = None) -> Tuple[float, str]:
    names, r = _flat(ref, keep)
    _, p = _flat(prog, keep)
    gap = np.abs(p - r) / np.maximum(r, np.median(r))
    i = int(np.argmax(gap))
    return float(gap[i]), names[i]


def numbers(prog: Dict, ref: Dict) -> Dict[str, Tuple[float, str]]:
    """``{name: (value, where)}`` for the three numbers compared."""
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    rel = np.abs(lp - lr) / np.abs(lr)
    i = int(np.argmax(rel))
    _, g = _flat(ref["grad_norms"])
    floor = ROUNDOFF_SHARE * np.median(g)
    keep = {k: np.asarray(v).ravel() >= floor
            for k, v in ref["grad_norms"].items()}
    return {"loss_gap": (float(rel[i]), f"step {i + 1}"),
            "grad_gap": worst_layer(prog["grad_norms"], ref["grad_norms"]),
            "update_gap": worst_layer(prog["change_norms"],
                                      ref["change_norms"], keep)}


def verdict(nums: Dict[str, Tuple[float, str]], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict]]:
    """Every number inside its limit, and the checks for the result line.
    A number that is not finite fails."""
    checks = {k: {"value": v, "limit": limits[k], "at": where}
              for k, (v, where) in nums.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
