"""Operations and bytes the router's work needs, counted from shapes.

Counts are of the work the algorithm needs on the real inputs: real
candidates and real score rows, never the padding a batch bucket or a
tile adds, and each weight matrix read once per call. They stay the same
whatever program computes them.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

F32 = 4
#: Operations per valid score of the four skew metrics and the decision:
#: min-max normalization (3), shift and normalization (2), running sum (1),
#: P test (1), entropy term (3), Gini weight and product (2), the sums (4).
DECISION_FLOPS_PER_SCORE = 16
#: Bytes written per request: tier, difficulty and four metrics.
DECISION_OUT_BYTES = 6 * F32


def scorer_flops(n_cand, d_triple: int, d_query: int, d_hidden: int) -> float:
    """Model FLOPs of scoring one question's ``n_cand`` candidates: the
    first layer on the triple and query halves, and the second layer.
    ``n_cand`` may be an array of questions; the FLOPs are summed."""
    n = np.asarray(n_cand, np.float64)
    per = 2.0 * n * (d_triple * d_hidden + d_hidden) + 2.0 * d_query * d_hidden
    return float(np.sum(per))


def scorer_kernel_work(n_cand, d_triple: int, d_hidden: int
                       ) -> tuple[float, float]:
    """(FLOPs, bytes) of one ``triple_score`` call over questions with
    ``n_cand`` real candidates each: both layers on every candidate; the
    features, each question's first-layer bias and the scores moved once,
    and the weights read once."""
    n = np.asarray(n_cand, np.float64)
    flops = float(np.sum(2.0 * n * (d_triple * d_hidden + d_hidden)))
    nbytes = float(np.sum(n * (d_triple + 1) * F32 + d_hidden * F32)
                   + (d_triple * d_hidden + d_hidden + 1) * F32)
    return flops, nbytes


def decision_work(n_valid) -> tuple[float, float]:
    """(FLOPs, bytes) of the decision over rows with ``n_valid`` real
    scores each: read the scores and the count, write the decision."""
    v = np.asarray(n_valid, np.float64)
    flops = float(np.sum(DECISION_FLOPS_PER_SCORE * v))
    nbytes = float(np.sum(v * F32 + F32 + DECISION_OUT_BYTES))
    return flops, nbytes


def load_peaks(device_kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; an unknown chip is an error."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})") from None


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute bound
    (bf16 peak) and the memory bound (HBM bandwidth)."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
