"""The numbers that decide ``correct``: each is 0 where the program agrees
with the reference, and grows with the size of a disagreement.

* ``missing``     requests due in the window that got no decision;
* ``metric_err``    largest gap of area, entropy, Gini or difficulty, as a
                    share of ``1 + |reference|``;
* ``cdf_gap``       where the cumulative-k counts differ, how far the
                    reference CDF lies from P at the count the program
                    misjudged (0 where they agree);
* ``decision_err``  largest difficulty gap, or where the tiers differ, the
                    reference difficulty's distance from the thresholds
                    between them;
* ``score_err``     largest gap of a served sigmoid score from the
                    reference's score of the same candidate, or of the worst
                    candidate kept below the reference's K-th best.

A check passes when every number is at most its limit.
"""

from __future__ import annotations

import numpy as np

from perfbench import reference

_CONTINUOUS = [reference.COLUMNS.index(c) for c in ("area", "entropy", "gini")]
_CUM = reference.COLUMNS.index("cumulative")


def metric_numbers(got_metrics, got_diff, ref_metrics, ref_cdf,
                   metric: str, p: float) -> dict:
    """``metric_err`` and ``cdf_gap`` of each request's metrics."""
    gm = np.asarray(got_metrics, np.float64)
    rm = np.asarray(ref_metrics, np.float64)
    if len(gm) == 0:
        return {"metric_err": 0.0, "cdf_gap": 0.0}
    rd = reference.difficulty(rm, metric)
    rel = np.abs(gm[:, _CONTINUOUS] - rm[:, _CONTINUOUS]) / (
        1.0 + np.abs(rm[:, _CONTINUOUS]))
    rel_d = np.abs(np.asarray(got_diff, np.float64) - rd) / (1.0 + np.abs(rd))
    metric_err = float(max(np.nan_to_num(rel, nan=np.inf).max(),
                           np.nan_to_num(rel_d, nan=np.inf).max()))
    kg, kr = gm[:, _CUM], rm[:, _CUM]
    ref_cdf = np.asarray(ref_cdf, np.float64)
    cdf_gap = 0.0
    for i in np.flatnonzero(kg != kr):
        g = kg[i]
        if not (np.isfinite(g) and g == int(g) and 1 <= g <= ref_cdf.shape[1]):
            return {"metric_err": metric_err, "cdf_gap": float("inf")}
        g = int(g)
        if g < kr[i]:     # the program says the CDF reached P at g
            gap = p - ref_cdf[i, g - 1]
        else:             # it says the CDF was still below P at g - 1
            gap = ref_cdf[i, g - 2] - p
        cdf_gap = max(cdf_gap, abs(float(gap)))
    return {"metric_err": metric_err, "cdf_gap": cdf_gap}


def decision_numbers(got_diff, got_tiers, ref_diff, ref_thresholds) -> dict:
    """``decision_err``: the largest of each request's difficulty gap (as a
    share of ``1 + |reference|``) and, where its tier differs from the
    reference's, the reference difficulty's distance from the thresholds
    between the two tiers."""
    rd = np.asarray(ref_diff, np.float64)
    gd = np.asarray(got_diff, np.float64)
    if len(rd) == 0:
        return {"decision_err": 0.0}
    err = float(np.nan_to_num(np.abs(gd - rd) / (1.0 + np.abs(rd)),
                              nan=np.inf).max())
    rt = reference.tiers(rd, ref_thresholds)
    gt = np.asarray(got_tiers)
    for i in np.flatnonzero(gt != rt):
        lo, hi = sorted((int(gt[i]), int(rt[i])))
        if lo < 0 or hi > len(ref_thresholds):
            return {"decision_err": float("inf")}
        err = max(err, float(np.max(np.abs(rd[i] - ref_thresholds[lo:hi]))))
    return {"decision_err": err}


def retrieval_numbers(got_idx, got_probs, got_nv, ref_logits) -> dict:
    """``score_err``: per question, the largest of the gaps between each
    served sigmoid score and the reference's score of the same candidate,
    and of how far the worst candidate kept scores (in the reference)
    below the reference's K-th best. ``ref_logits``: one [n_cand] array
    per question."""
    err = 0.0
    for idx, probs, nv, logits in zip(got_idx, got_probs, got_nv,
                                      ref_logits):
        k = min(len(idx), len(logits))
        nv = int(nv)
        kept = np.asarray(idx[:nv])
        if nv != k or kept.min(initial=0) < 0 or \
                kept.max(initial=0) >= len(logits) or \
                len(np.unique(kept)) != nv:
            return {"score_err": float("inf")}
        ref = reference.sigmoid(logits)
        kth = np.sort(ref)[::-1][k - 1]
        err = max(err, float(kth - ref[kept].min()), float(np.max(np.abs(
            np.asarray(probs[:nv], np.float64) - ref[kept]))))
    return {"score_err": err}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(all within limits, {name: {"value", "limit"}}) in ``limits``
    order; a number without a limit is an error."""
    missing = set(numbers) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)}")
    table = {name: {"value": float(numbers[name]), "limit": float(limit)}
             for name, limit in limits.items() if name in numbers}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
