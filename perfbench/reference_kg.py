"""The plain reference of questions over a knowledge graph held on the
chip: SubgraphRAG's triple features (arXiv:2410.20724) with the
directional distance encoding (DDE) taken over the question's own
subgraph, its candidate triples, in place of the whole graph.

Imports nothing of the program. Every function runs in float64 on the
host; with `perfbench.reference` (``candidates``, ``mlp_logits``,
``top_k``, ``sigmoid``, ``skew_metrics``, ``calibrate``, ``tiers``) it is
the whole path from candidate ids to tiers.
"""

from __future__ import annotations

import numpy as np

from perfbench.reference import MAX_HOPS, Graph


def subgraph_distance(g: Graph, topic: int, edges: np.ndarray
                      ) -> dict[int, int]:
    """Breadth-first hop distances from ``topic`` over the directed edges
    head -> tail of the triples ``edges`` alone, up to ``MAX_HOPS``."""
    out: dict[int, list[int]] = {}
    for e in np.asarray(edges).tolist():
        out.setdefault(int(g.heads[e]), []).append(int(g.tails[e]))
    dist, frontier = {int(topic): 0}, [int(topic)]
    for level in range(1, MAX_HOPS + 1):
        nxt = []
        for node in frontier:
            for t in out.get(node, ()):
                if t not in dist:
                    dist[t] = level
                    nxt.append(t)
        frontier = nxt
    return dist


def features(g: Graph, ent: np.ndarray, rel: np.ndarray, topic: int,
             query: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """[n, 3d + 2(MAX_HOPS+1) + 4] float64 features of the candidate
    triples ``edges`` of one question: head, relation and tail rows, the
    one-hot subgraph distances of head and tail from the topic (buckets
    0..3 and ">= 4 or unreachable"), and q.h, q.r, q.t, q.(h+r) over
    sqrt(d)."""
    h = ent[g.heads[edges]].astype(np.float64)
    r = rel[g.rels[edges]].astype(np.float64)
    t = ent[g.tails[edges]].astype(np.float64)
    dist = subgraph_distance(g, topic, edges)
    eye = np.eye(MAX_HOPS + 1)

    def dde(nodes):
        return eye[[min(dist.get(int(x), MAX_HOPS), MAX_HOPS) for x in nodes]]

    qv = query.astype(np.float64) / np.sqrt(h.shape[1])
    sim = np.stack([h @ qv, r @ qv, t @ qv, (h + r) @ qv], axis=1)
    return np.concatenate([h, r, t, dde(g.heads[edges]), dde(g.tails[edges]),
                           sim], axis=1)
