"""The knowledge graph held on the device, and the plain reference of the
features the router builds from it.

:class:`DeviceKG` keeps the entity and relation embedding tables and the
triples (``heads``/``rels``/``tails``) in device memory, put there once.
A question then arrives as its topic entity, its query embedding and the
ids of its candidate triples (its retrieved subgraph); the decision
program (`repro.core.router.route_kg`) gathers every feature it needs
from these tables, so nothing the size of a feature block crosses from
the host.

The features are SubgraphRAG's (arXiv:2410.20724): head, relation and
tail embeddings, the directional distance encoding (DDE) of head and tail,
and four query similarities. DDE is the one-hot hop distance from the
topic entity over the question's own subgraph: its candidate triples, as
directed edges head -> tail, searched breadth-first. Distances 0 .. 3 are
exact; 4 or more, or unreachable, share the last bucket.

:func:`subgraph_distances` and :func:`question_features` are the plain
NumPy reference of those semantics, one question at a time, for tests.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.retrieval.scorer import MAX_DDE_HOPS

#: Candidate slots of a question unless the store is told otherwise: a
#: SubgraphRAG-sized subgraph.
DEFAULT_MAX_CANDS = 512


@dataclasses.dataclass(frozen=True)
class DeviceKG:
    """Entity and relation tables and the triples, on one device.

    ``max_cands`` is the candidate slots of every question: each call is
    padded to it, so each batch bucket compiles one program whatever the
    candidate counts of its questions.
    """

    ent: jax.Array      # [n_entities, d] float32
    rel: jax.Array      # [n_relations, d] float32
    heads: jax.Array    # [n_triples] int32
    rels: jax.Array     # [n_triples] int32
    tails: jax.Array    # [n_triples] int32
    max_cands: int = DEFAULT_MAX_CANDS

    @classmethod
    def put(cls, ent, rel, heads, rels, tails,
            max_cands: int = DEFAULT_MAX_CANDS, device=None) -> "DeviceKG":
        """Move host tables to ``device`` (the default device) in one
        transfer and wait until they are there."""
        if np.shape(ent)[1] != np.shape(rel)[1]:
            raise ValueError(f"entity width {np.shape(ent)[1]} != relation "
                             f"width {np.shape(rel)[1]}")
        if not (len(heads) == len(rels) == len(tails)):
            raise ValueError("heads, rels and tails differ in length")
        if max_cands < 1:
            raise ValueError(f"max_cands must be >= 1, got {max_cands}")
        arrays = jax.device_put(
            (np.asarray(ent, np.float32), np.asarray(rel, np.float32),
             np.asarray(heads, np.int32), np.asarray(rels, np.int32),
             np.asarray(tails, np.int32)), device)
        jax.block_until_ready(arrays)
        return cls(*arrays, max_cands=int(max_cands))

    @property
    def d_emb(self) -> int:
        return int(self.ent.shape[1])

    @property
    def n_entities(self) -> int:
        return int(self.ent.shape[0])

    @property
    def n_triples(self) -> int:
        return int(self.heads.shape[0])

    @property
    def nbytes(self) -> int:
        """Device bytes of the five arrays."""
        return int(sum(a.nbytes for a in (self.ent, self.rel, self.heads,
                                          self.rels, self.tails)))


# -- the plain reference ----------------------------------------------------


def subgraph_distances(topic: int, heads: np.ndarray,
                       tails: np.ndarray) -> dict[int, int]:
    """Breadth-first hop distances from ``topic`` over the directed edges
    ``heads[i] -> tails[i]``, up to ``MAX_DDE_HOPS`` levels."""
    out: dict[int, list[int]] = {}
    for h, t in zip(np.asarray(heads).tolist(), np.asarray(tails).tolist()):
        out.setdefault(h, []).append(t)
    dist, frontier = {int(topic): 0}, [int(topic)]
    for level in range(1, MAX_DDE_HOPS + 1):
        nxt = []
        for node in frontier:
            for t in out.get(node, ()):
                if t not in dist:
                    dist[t] = level
                    nxt.append(t)
        frontier = nxt
    return dist


def question_features(ent: np.ndarray, rel: np.ndarray, heads: np.ndarray,
                      rels: np.ndarray, tails: np.ndarray, topic: int,
                      query: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """[n, 3d + 2(MAX_DDE_HOPS+1) + 4] float32 features of one question's
    candidate triples ``edges``: head, relation and tail rows, the DDE
    one-hots of head and tail over the subgraph ``edges`` itself, and
    q.h, q.r, q.t, q.(h+r) over sqrt(d)."""
    hid, rid, tid = heads[edges], rels[edges], tails[edges]
    h, r, t = ent[hid], rel[rid], ent[tid]
    dist = subgraph_distances(topic, hid, tid)
    eye = np.eye(MAX_DDE_HOPS + 1, dtype=np.float32)

    def dde(nodes):
        return eye[[min(dist.get(int(x), MAX_DDE_HOPS), MAX_DDE_HOPS)
                    for x in nodes]]

    qv = np.asarray(query, np.float32) / np.sqrt(np.float32(h.shape[1]))
    sim = np.stack([h @ qv, r @ qv, t @ qv, (h + r) @ qv], axis=1)
    return np.concatenate([h, r, t, dde(hid), dde(tid), sim],
                          axis=1).astype(np.float32)
