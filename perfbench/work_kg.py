"""Operations and bytes of routing questions over a knowledge graph held
on the chip, counted from shapes.

As in `perfbench.work`: the work the algorithm needs on the real inputs
(real candidates, never the slots a batch bucket or ``max_cands`` pads),
with each weight matrix read once per call.
"""

from __future__ import annotations

import numpy as np

from perfbench import work
from perfbench.reference import MAX_HOPS

F32 = work.F32
#: Operations per real candidate of the four query similarities: q.h,
#: q.r, q.t, q.(h+r), a multiply and an add per element, and h+r itself.
SIM_FLOPS_PER_ELEMENT = 9


def d_triple(d_emb: int) -> int:
    """Feature width: 3 embeddings, 2 DDE one-hots, 4 similarities."""
    return 3 * d_emb + 2 * (MAX_HOPS + 1) + 4


def question_work(n_cand_calls, d_emb: int, d_hidden: int, top_k: int
                  ) -> tuple[float, float]:
    """(FLOPs, bytes) of the calls whose questions had ``n_cand_calls[c]``
    real candidates each.

    FLOPs: the scorer's (`work.scorer_flops`), the similarities, and the
    decision over each question's top ``min(n_cand, top_k)`` scores.
    Bytes: per real candidate its id, its triple's three entity and
    relation ids and three gathered rows of ``d_emb`` floats; per question
    its topic, candidate count and query embedding read, its decision, K
    scores and K triple ids written; per call the scorer weights read
    once."""
    dt = d_triple(d_emb)
    weights = (dt * d_hidden + d_emb * d_hidden + 2 * d_hidden + 1) * F32
    flops = nbytes = 0.0
    for n_cand in n_cand_calls:
        n = np.asarray(n_cand, np.float64)
        flops += (work.scorer_flops(n, dt, d_emb, d_hidden)
                  + float(np.sum(n)) * SIM_FLOPS_PER_ELEMENT * d_emb
                  + work.decision_work(np.minimum(n, top_k))[0])
        nbytes += (weights
                   + float(np.sum(n)) * (4 + 3 * d_emb) * F32
                   + len(n) * ((2 + d_emb + 2 * top_k) * F32
                               + work.DECISION_OUT_BYTES))
    return flops, nbytes
