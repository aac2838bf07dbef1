"""The comparisons that decide `correct`, each number beside its limit.

Limits live in `bench/limits/<cell>.json`, each set between the largest
reading of sound runs and the smallest reading of the control or of a
planted fault (PERF.md gives both readings for every limit).
"""
from __future__ import annotations

import json
import math
import os
import statistics

from bench.spec import BENCH

# a leaf whose reference gradient is under this share of the median
# leaf's is nought to rounding (a bias under a normalization): Adam
# moves it by round-off alone, so neither its gradient nor its change
# is compared
NEGLIGIBLE_LEAF = 1e-3


def limits(cell: str) -> dict:
    path = os.path.join(BENCH, "limits", cell + ".json")
    with open(path) as f:
        return json.load(f)["limits"]


def _worst_leaf_gap(prog: list, ref: list, keep: list) -> float:
    """Largest |norm_prog - norm_ref| over the kept leaves, each against
    the larger of its own reference norm and the median leaf's."""
    kept = [r for r, k in zip(ref, keep) if k]
    med = statistics.median(kept)
    return max(abs(p - r) / max(r, med)
               for p, r, k in zip(prog, ref, keep) if k)


def train_checks(losses, grads, change, r_losses, r_grads, r_change,
                 lims: dict) -> dict:
    """loss_gap: the worst relative gap of a check step's loss;
    grad_norm_gap: the worst leaf's gap of the first gradient's norm;
    update_norm_gap: the worst leaf's gap of the norm of its change
    over the check steps."""
    med = statistics.median(r_grads)
    keep = [g >= NEGLIGIBLE_LEAF * med for g in r_grads]
    values = {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(losses, r_losses)),
        "grad_norm_gap": _worst_leaf_gap(grads, r_grads, keep),
        "update_norm_gap": _worst_leaf_gap(change, r_change, keep),
    }
    return {k: {"value": v, "limit": lims.get(k)} for k, v in values.items()}


def serve_checks(readings: dict, traffic: dict, lims: dict) -> dict:
    """logit_gap: the widest gap by which a served greedy token's
    reference logit lies below the reference's best at its position.
    Where the mix samples, also
    nucleus_excess: the most by which a sampled token's exclusive
    cumulative probability under the reference (most likely first, at
    temperature 1) exceeds top_p, 0 where every one lies inside the
    reference's nucleus;
    sampled_logit_z: |sum of (the sampled token's reference logit minus
    the mean logit of the reference's nucleus at the stated
    temperature)| over the square root of the sum of that
    distribution's variances: about |N(0, 1)| for a sampler that draws
    what it is asked to, large for one at another temperature.
    No token compared reads as no number, which is not correct."""
    gaps = readings["greedy"]["gap"]
    out = {"logit_gap": {"value": max(gaps) if gaps else None,
                         "limit": lims.get("logit_gap")}}
    if traffic["sampling"].get("temperature", 0.0) > 0:
        s = readings["sampled"]
        excess = max(max(s["excess"]), 0.0) if s["excess"] else None
        z = abs(sum(s["dev"])) / math.sqrt(sum(s["var"])) \
            if s["var"] and sum(s["var"]) > 0 else None
        out["nucleus_excess"] = {"value": excess,
                                 "limit": lims.get("nucleus_excess")}
        out["sampled_logit_z"] = {"value": z,
                                  "limit": lims.get("sampled_logit_z")}
    return out


def correct(checks: dict) -> bool:
    """Every number there and within its limit."""
    return all(c["value"] is not None and c["limit"] is not None
               and c["value"] <= c["limit"] for c in checks.values())
