"""The numbers that decide ``correct``, each beside its limit.

Training (the first three steps, program against reference).  Each leaf's
gap is the distance between the program's and the reference's norm of
that leaf, against the reference's norm of the leaf or of the median
leaf, whichever is larger:

* ``change_median`` / ``change_gap``: the median and the largest leaf gap
  of the change of the parameters after three steps.  Leaves whose
  reference gradient is under a thousandth of the median leaf's move
  under Adam by round-off alone and are left out.
* ``grad_median`` / ``grad_gap``: the same of the first gradient as AdamW
  got it (clipped).
* ``loss_gap``: the largest relative gap of the loss of the steps before
  the first update lands (the schedule's warm-up gives step 0 a learning
  rate of 0, so steps 0 and 1 run the initial weights).
* ``grad_norm_gap``: the same of the gradient's global norm before
  clipping, as the step reports it.  A mean over fewer rows has a larger
  norm; AdamW's update hides that in the change.
* ``small_change_gap`` / ``small_grad_gap``: the largest gap of the
  change and of the first gradient among the leaves left out above, each
  against the reference's norm of that leaf alone.  These are the PRC
  clipping ratios (one per layer and matrix), whose gradient is a sum
  over the few clipped entries: small beside a whole matrix's, but not
  nought.

The cell's limits name the numbers compared; the others are reported.
ALS-PoTQ rounds to the nearest code, so a rounding-level difference can
flip a code and the flips spread; the medians are the numbers that stay
steady from seed to seed.

Serving (a sample of the served tokens, teacher-forced through the
reference):

* ``argmax_share``: the share of served tokens that are the reference's
  first choice at their position.  Sound when at least its limit.
* ``logit_gap`` / ``median_gap``: the widest and the median gap by which
  a served token's logit in the reference lies below the reference's best
  logit at that position.
"""
from __future__ import annotations

import statistics
from typing import Dict

import numpy as np

TINY_LEAF = 1e-3
FORWARD_STEPS = 2
#: numbers that are sound when at least their limit; the rest when at most
AT_LEAST = frozenset({"argmax_share"})


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], keep):
    """Per leaf: |program norm - reference norm| over the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    med = statistics.median(ref.values())
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def train_numbers(prog: dict, ref: dict, limits: dict) -> dict:
    """Every reading of the checked steps; those the cell gives a limit
    are the numbers compared, the rest are reported beside them."""
    if set(prog["grad_norms"]) != set(ref["grad_norms"]):
        raise RuntimeError("program and reference leaves differ: "
                           f"{sorted(prog['grad_norms'])} vs "
                           f"{sorted(ref['grad_norms'])}")
    loss = [abs(p - r) / abs(r) for p, r in zip(prog["losses"],
                                                  ref["losses"])]
    gnorm = [abs(p - r) / abs(r) for p, r in zip(prog["global_norms"],
                                                   ref["global_norms"])]
    med = statistics.median(ref["grad_norms"].values())
    moved = [k for k, g in ref["grad_norms"].items() if g >= TINY_LEAF * med]
    grad = _leaf_gaps(prog["grad_norms"], ref["grad_norms"],
                      ref["grad_norms"])
    change = _leaf_gaps(prog["change_norms"], ref["change_norms"], moved)
    small = sorted(set(ref["grad_norms"]) - set(moved))
    own = lambda key: {k: abs(prog[key][k] - ref[key][k])  # noqa: E731
                       / max(ref[key][k], 1e-30) for k in small}
    small_change, small_grad = own("change_norms"), own("grad_norms")
    readings = {
        "change_median": statistics.median(change.values()),
        "change_gap": max(change.values()),
        "grad_median": statistics.median(grad.values()),
        "grad_gap": max(grad.values()),
        "loss_gap": max(loss[:FORWARD_STEPS]),
        "grad_norm_gap": max(gnorm[:FORWARD_STEPS]),
        "small_change_gap": max(small_change.values(), default=0.0),
        "small_grad_gap": max(small_grad.values(), default=0.0),
    }
    return {
        "numbers": {k: v for k, v in readings.items() if k in limits},
        "limits": {k: limits[k] for k in readings if k in limits},
        "detail": {"readings": readings, "loss_gaps": loss,
                   "grad_norm_gaps": gnorm,
                   "grad_leaf": max(grad, key=grad.get),
                   "change_leaf": max(change, key=change.get),
                   "left_out": small},
    }


def logit_gaps(ref_logits, tokens) -> np.ndarray:
    """Per served token: reference best minus reference logit of the
    token served.  ``ref_logits`` (T, V), ``tokens`` (T,)."""
    ref_logits = np.asarray(ref_logits, np.float64)
    picked = ref_logits[np.arange(len(tokens)), np.asarray(tokens)]
    return ref_logits.max(axis=-1) - picked


def serve_numbers(gaps, limits: dict, n_tokens: int, n_requests: int):
    """``gaps``: per sampled request, the gap of each served token."""
    every = np.concatenate(gaps) if gaps else np.array([np.inf])
    readings = {"argmax_share": float(np.mean(every == 0.0)),
                "logit_gap": float(np.max(every)),
                "median_gap": float(np.median(every))}
    return {"numbers": {k: v for k, v in readings.items() if k in limits},
            "limits": {k: limits[k] for k in readings if k in limits},
            "detail": {"readings": readings, "tokens_compared": n_tokens,
                       "requests_compared": n_requests}}


def sound(name: str, value: float, limit: float) -> bool:
    if not np.isfinite(value):
        return False
    return value >= limit if name in AT_LEAST else value <= limit


def passed(result: dict) -> bool:
    return all(sound(k, v, result["limits"][k])
               for k, v in result["numbers"].items())
