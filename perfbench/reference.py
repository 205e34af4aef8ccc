"""The plain reference the benchmark holds the router to.

A straightforward implementation of the router's semantics that imports
nothing of the program:

* the four skew metrics of SkewRoute (arXiv:2505.23841, §3.2-3.3) over a
  valid prefix of descending top-K scores, the difficulty that orients
  them (larger is harder), and the quantile calibration of thresholds;
* SubgraphRAG-style retrieval (arXiv:2410.20724): the candidate pool of a
  question, its triple features (head, relation and tail embeddings, the
  one-hot hop distances of head and tail from the topic entity, four
  query similarities) and the two-layer MLP that scores them.

Every function runs in float64 on the host by default. The skew metrics
take an array module and a dtype, and ``mlp_logits`` a matmul, so that the
control (the same reference one precision lower) runs the same code on
the chip.
"""

from __future__ import annotations

from typing import Callable, Sequence

import numpy as np

EPS = 1e-12
MAX_HOPS = 4  # hop-distance buckets 0..3 and ">= 4 or unreachable"
COLUMNS = ("area", "cumulative", "entropy", "gini")


# -- skew metrics ---------------------------------------------------------


def skew_metrics(scores, n_valid, p: float, xp=np, dtype=np.float64):
    """[B, K] descending scores with [B] valid-prefix counts ->
    ([B, 4] metrics in ``COLUMNS`` order, [B, K] CDF of the normalized
    scores)."""
    s = xp.asarray(scores).astype(dtype)
    k = s.shape[1]
    valid = xp.arange(k)[None, :] < xp.asarray(n_valid)[:, None]
    n = xp.sum(valid, axis=1).astype(dtype)
    lo = xp.min(xp.where(valid, s, xp.inf), axis=1, keepdims=True)
    hi = xp.max(xp.where(valid, s, -xp.inf), axis=1, keepdims=True)
    area = xp.sum(xp.where(valid, (s - lo) / (hi - lo + EPS), 0.0), axis=1)
    shifted = xp.where(valid, s - xp.minimum(lo, 0.0), 0.0)
    total = xp.sum(shifted, axis=1, keepdims=True)
    prob = shifted / (total + EPS)
    cdf = xp.cumsum(-xp.sort(-prob, axis=1), axis=1)
    reached = cdf >= p - EPS
    cum_k = xp.where(xp.any(reached, axis=1),
                     xp.argmax(reached, axis=1) + 1, n).astype(dtype)
    entropy = -xp.sum(xp.where(prob > EPS, prob * xp.log2(prob + EPS), 0.0),
                      axis=1)
    asc = xp.sort(shifted, axis=1)
    rank = xp.maximum(xp.arange(1, k + 1)[None, :] - (k - n)[:, None], 0.0)
    weight = xp.where(rank > 0, n[:, None] - rank + 1.0, 0.0)
    gini = (n + 1.0 - 2.0 * xp.sum(weight * asc, axis=1) / (total[:, 0] + EPS)
            ) / xp.maximum(n, 1.0)
    gini = xp.clip(gini, 0.0, 1.0)
    return xp.stack([area, cum_k, entropy, gini], axis=1), cdf


def difficulty(metrics, metric: str):
    """Orient a metric column so that larger means harder: a high Gini
    coefficient is a skewed, easy score list, so Gini is negated."""
    col = metrics[:, COLUMNS.index(metric)]
    return -col if metric == "gini" else col


def calibrate(diff: np.ndarray, shares: Sequence[float]) -> np.ndarray:
    """Thresholds that split ``diff`` into tiers with the given shares:
    the cumulative shares' quantiles (linear interpolation), made
    ascending."""
    cuts = np.cumsum(np.asarray(shares, np.float64))[:-1]
    thr = np.quantile(np.asarray(diff, np.float64), cuts)
    return np.maximum.accumulate(thr)


def tiers(diff: np.ndarray, thresholds: np.ndarray) -> np.ndarray:
    """Tier = number of thresholds strictly below the difficulty."""
    return np.sum(np.asarray(diff)[:, None] > thresholds[None, :], axis=1)


# -- retrieval --------------------------------------------------------------


class Graph:
    """A triple store with its out-edge lists, in head order (stable)."""

    def __init__(self, heads: np.ndarray, rels: np.ndarray,
                 tails: np.ndarray, n_entities: int, n_relations: int):
        self.heads, self.rels, self.tails = heads, rels, tails
        self.n_entities, self.n_relations = n_entities, n_relations
        self.order = np.argsort(heads, kind="stable")
        self.offsets = np.concatenate(
            [[0], np.cumsum(np.bincount(heads, minlength=n_entities))])

    def out_edges(self, node: int) -> np.ndarray:
        return self.order[self.offsets[node]:self.offsets[node + 1]]

    def khop_edges(self, seed: int, hops: int, max_edges: int) -> list[int]:
        """Edges met by a breadth-first walk of ``hops`` levels from
        ``seed``, in visiting order, cut at ``max_edges``."""
        frontier, seen, edges = [seed], {seed}, []
        for _ in range(hops):
            nxt = []
            for node in frontier:
                for e in self.out_edges(node):
                    if len(edges) >= max_edges:
                        return edges
                    edges.append(int(e))
                    t = int(self.tails[e])
                    if t not in seen:
                        seen.add(t)
                        nxt.append(t)
            frontier = nxt
            if not frontier:
                break
        return edges

    def hop_distance(self, seed: int, max_hops: int) -> dict[int, int]:
        dist, frontier = {seed: 0}, [seed]
        for h in range(1, max_hops + 1):
            nxt = []
            for node in frontier:
                for e in self.out_edges(node):
                    t = int(self.tails[e])
                    if t not in dist:
                        dist[t] = h
                        nxt.append(t)
            frontier = nxt
            if not frontier:
                break
        return dist


def candidates(g: Graph, topic: int, hops: int, gold: np.ndarray,
               max_edges: int, seed: int) -> np.ndarray:
    """A question's candidate pool: its gold chain, the walk of
    ``max(hops, 2)`` levels from the topic (half the pool at most) and
    random triples to fill it, deduplicated and shuffled by a generator
    seeded with ``seed + topic``."""
    rng = np.random.default_rng(seed + topic)
    local = np.asarray(g.khop_edges(topic, max(hops, 2), max_edges // 2),
                       np.int32)
    n_rand = max(max_edges - len(local) - len(gold), 0)
    randoms = rng.integers(0, len(g.heads), n_rand).astype(np.int32)
    pool = np.unique(np.concatenate([gold, local, randoms]))
    rng.shuffle(pool)
    return pool[:max_edges]


def features(g: Graph, ent: np.ndarray, rel: np.ndarray, topic: int,
             query: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """[n, 3d + 2(MAX_HOPS+1) + 4] float64 features of the candidate
    triples ``edges`` for one question."""
    h = ent[g.heads[edges]].astype(np.float64)
    r = rel[g.rels[edges]].astype(np.float64)
    t = ent[g.tails[edges]].astype(np.float64)
    dist = g.hop_distance(topic, MAX_HOPS)
    eye = np.eye(MAX_HOPS + 1)
    dde_h = eye[[min(dist.get(int(x), MAX_HOPS), MAX_HOPS)
                 for x in g.heads[edges]]]
    dde_t = eye[[min(dist.get(int(x), MAX_HOPS), MAX_HOPS)
                 for x in g.tails[edges]]]
    qv = query.astype(np.float64) / np.sqrt(h.shape[1])
    sim = np.stack([h @ qv, r @ qv, t @ qv, (h + r) @ qv], axis=1)
    return np.concatenate([h, r, t, dde_h, dde_t, sim], axis=1)


def _matmul64(a, b):
    return np.asarray(a, np.float64) @ np.asarray(b, np.float64)


def mlp_logits(feats, query, w: dict,
               matmul: Callable = _matmul64) -> np.ndarray:
    """relu(feats @ w1_t + query @ w1_q + b1) @ w2 + b2 -> [n] logits."""
    hidden = np.maximum(
        np.asarray(matmul(feats, w["w1_t"]))
        + np.asarray(matmul(query[None, :], w["w1_q"]))
        + np.asarray(w["b1"], np.float64)[None, :], 0.0)
    return (np.asarray(matmul(hidden, w["w2"]))[:, 0]
            + float(np.asarray(w["b2"]).reshape(-1)[0]))


def top_k(logits: np.ndarray, k: int) -> np.ndarray:
    """Indices of the ``k`` largest logits, largest first (lower index
    first among equal logits)."""
    return np.argsort(-logits, kind="stable")[:k]


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64)))
