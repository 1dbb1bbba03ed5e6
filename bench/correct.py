"""The comparison that decides a run's ``correct``.

Each number is compared where the cell's file under ``bench/workloads/``
gives it a limit, and reported beside the others where it does not:

* ``first_loss_gap`` -- the relative gap of round 1's loss between the
  program and the reference: the forward pass at the seed's weights;
* ``later_loss_gap`` -- the largest relative gap of the later rounds'
  losses: the forward pass after the quantized aggregate and AdamW moved
  the weights;
* ``grad_gap`` -- the worst leaf's gap between the norms of the aggregate
  gradient the optimizer got in round 1 (read back from AdamW's first moment),
  over the reference leaf's norm or the median leaf's, whichever is larger;
* ``grad_gap_median`` -- the same gap of the median leaf: steady from seed
  to seed where the worst leaf swings with one leaf's largest element;
* ``update_gap`` -- the same measure of the master weights' change over the
  first rounds, leaving out leaves whose reference gradient is below a
  thousandth of the median leaf's (they move by round-off alone);
* ``code_mismatch`` -- the share (%) of all coordinates whose b-bit code
  in worker 0's ``qhat`` after round 1 differs from the reference's: the
  wire's quantization element by element, so the direction of the
  aggregate, where the norms above see only its size.  Coordinates whose
  reference innovation is exactly 0 (an embedding row no token of the
  batch uses) sit on eq. 5's tie between two codes and are left out;
* ``uploads_mismatch`` -- rounds in which the program's upload count differs
  from the reference's (an exact comparison, limit 0);
* ``bits_mismatch`` -- rounds in which the wire bits the program counts
  differ from eq. 6's ``32 R + b p`` per upload (exact up to the float32
  the program counts in: a relative 1e-6; limit 0).
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("first_loss_gap", "later_loss_gap", "grad_gap", "grad_gap_median",
           "update_gap", "code_mismatch", "uploads_mismatch", "bits_mismatch")
ROUNDOFF_LEAF = 1e-3


def leaf_gaps(got, want, keep=None):
    """Per leaf: the gap between the norms over the reference leaf's norm
    or the median leaf's, whichever is larger (0 where ``keep`` is off)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if keep is None:
        keep = np.ones(want.shape, bool)
    floor = np.median(want[keep])
    gap = np.abs(got - want) / np.maximum(want, floor)
    return np.where(keep, gap, 0.0)


def _worst_leaf(got, want, keep=None):
    gap = leaf_gaps(got, want, keep)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def readings(got: dict, want: dict) -> dict:
    """Each number of ``got`` (the program, or a control or fault in its
    place) against ``want`` (the reference), with the leaf it came from."""
    gaps = [abs(g - w) / abs(w) for g, w in zip(got["losses"], want["losses"])]
    grad, gi = _worst_leaf(got["agg_norms"], want["agg_norms"])
    g = np.asarray(want["grad_norms"], np.float64)
    moving = g >= ROUNDOFF_LEAF * np.median(g)
    upd, ui = _worst_leaf(got["change_norms"], want["change_norms"], moving)
    mism = sum(int(a != b) for a, b in zip(got["uploads"], want["uploads"]))
    bits = sum(int(abs(a - b) > 1e-6 * max(abs(b), 1.0))
               for a, b in zip(got["bits"], want["bits"]))
    ties = want["ties"]
    differ = [np.count_nonzero((np.asarray(a) != np.asarray(b)) & ~t)
              for a, b, t in zip(got["codes"], want["codes"], ties)]
    counted = [t.size - np.count_nonzero(t) for t in ties]
    names = want.get("names")
    ci = int(np.argmax([d / max(n, 1) for d, n in zip(differ, counted)]))
    return {"first_loss_gap": gaps[0], "later_loss_gap": max(gaps[1:]),
            "grad_gap": grad,
            "grad_gap_median": float(np.median(leaf_gaps(got["agg_norms"],
                                                         want["agg_norms"]))),
            "update_gap": upd,
            "code_mismatch": 100.0 * sum(differ) / max(sum(counted), 1),
            "code_ties": int(sum(t.size for t in ties) - sum(counted)),
            "uploads_mismatch": mism, "bits_mismatch": bits,
            "grad_gap_leaf": names[gi] if names else gi,
            "code_mismatch_leaf": names[ci] if names else ci,
            "update_gap_leaf": names[ui] if names else ui,
            "left_out": [n for n, k in zip(names or range(len(g)), moving)
                         if not k]}


def judge(numbers: dict, limits: dict):
    """``(correct, checks)``: each number the cell's limits name, beside its
    limit; correct when none is above its limit or missing.  A number the
    cell gives no limit is reported and not compared."""
    checks = {k: {"value": numbers.get(k), "limit": limits[k]}
              for k in NUMBERS if k in limits}
    ok = all(c["value"] is not None and np.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
