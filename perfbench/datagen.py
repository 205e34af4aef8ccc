"""Seeded data generators, kept with the benchmark so no later change to the
program can move them.

Copied from the program's generators, with the changes each docstring names:

* ``power_law_rows``: `repro.serving.loadgen.workload._power_law_scores`
  (pre-scored top-K rows), plus a ragged share of rows with fewer than K
  valid scores.
* ``make_kg``: `repro.retrieval.synthetic.make_kg`, returning the
  reference's `Graph` of the triples. The tail search runs at the narrow
  structural width, in chunks of edges on threads; the wide entity and
  relation tables are projected from the narrow ones, so that a 1024-wide
  deployment never builds the ``[E, 16, d]`` search array.
* ``make_queries``: `repro.retrieval.synthetic.make_queries` over that
  graph.
"""

from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench.reference import Graph

#: edges per step of `make_kg`'s tail search
CHUNK = 4096

HOP_MIX = {
    "webqsp": {1: 0.655, 2: 0.345},
    "cwq": {1: 0.409, 2: 0.383, 3: 0.147, 4: 0.061},
}


def power_law_rows(rng: np.random.Generator, n: int, k: int,
                   alpha_lo: float, alpha_hi: float, ragged_share: float = 0.0,
                   ragged_lo: int = 1, ragged_hi: int = 1
                   ) -> tuple[np.ndarray, np.ndarray]:
    """``n`` descending top-``k`` score rows and their valid counts.

    Each row decays as ``rank ** -alpha`` with ``alpha ~ U[lo, hi]`` and 5%
    multiplicative noise (flat rows are hard questions, spiky rows easy
    ones). A ``ragged_share`` of the rows keeps only ``U{ragged_lo ..
    ragged_hi}`` valid scores (questions with fewer than ``k`` triples);
    the scores past that count are zero, as a retriever pads them.
    """
    alphas = rng.uniform(alpha_lo, alpha_hi, n)
    base = 1.0 / np.arange(1, k + 1)[None, :] ** alphas[:, None]
    noise = rng.uniform(0.95, 1.05, (n, k))
    rows = np.sort((base * noise).astype(np.float32), axis=1)[:, ::-1].copy()
    n_valid = np.full(n, k, np.int32)
    ragged = rng.uniform(0.0, 1.0, n) < ragged_share
    n_valid[ragged] = rng.integers(ragged_lo, ragged_hi + 1, int(ragged.sum()))
    rows[np.arange(k)[None, :] >= n_valid[:, None]] = 0.0
    return rows, n_valid


@dataclasses.dataclass
class Question:
    """The fields the program's feature builder reads from a question."""

    topic: int
    query_emb: np.ndarray
    gold_edges: np.ndarray
    answer: int
    hops: int


def make_kg(n_entities: int, n_relations: int, avg_degree: float,
            structure_dim: int, width: int, seed: int
            ) -> tuple[Graph, np.ndarray, np.ndarray]:
    """Power-law out-degree KG with compositional tails
    (``tail ~ head + relation``), found at ``structure_dim``.

    Returns the graph and ``width``-wide entity and relation tables: the
    narrow embeddings through one random projection, so the compositional
    structure holds at the wide width too.

    The draws come in the original order: the candidate tails whole, then
    the noise ``CHUNK`` edges at a time, each chunk's float64 search
    (``norm(ent[pool] - target)``, argmin over the 16 candidates) running
    on a thread while the next chunk is drawn. The search never holds the
    ``[n_edges, 16, structure_dim]`` array.
    """
    rng = np.random.default_rng(seed)
    ent = rng.normal(0, 1, (n_entities, structure_dim)).astype(np.float32)
    rel = rng.normal(0, 1, (n_relations, structure_dim)).astype(np.float32)
    deg = np.minimum(rng.zipf(1.7, n_entities), 200)
    deg = np.maximum((deg * avg_degree / deg.mean()).astype(np.int64), 1)
    n_edges = int(deg.sum())
    heads = np.repeat(np.arange(n_entities, dtype=np.int32), deg)
    rels = rng.integers(0, n_relations, n_edges).astype(np.int32)
    pool = rng.integers(0, n_entities, (n_edges, 16))
    ent64 = ent.astype(np.float64)
    tails = np.empty(n_edges, np.int32)

    def search(s: int, noise: np.ndarray) -> None:
        e = s + len(noise)
        target = ent[heads[s:e]] + rel[rels[s:e]] + noise
        d = ent64[pool[s:e]]
        d -= target[:, None, :]
        d *= d
        dists = np.sqrt(np.add.reduce(d, axis=-1))
        tails[s:e] = pool[np.arange(s, e), dists.argmin(1)]

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as ex:
        for f in [ex.submit(search, s, rng.normal(
                      0, 0.3, (min(CHUNK, n_edges - s), structure_dim)))
                  for s in range(0, n_edges, CHUNK)]:
            f.result()
    del pool, ent64
    proj = rng.normal(0, structure_dim ** -0.5,
                      (structure_dim, width)).astype(np.float32)
    return (Graph(heads, rels, tails, n_entities, n_relations),
            ent @ proj, rel @ proj)


def make_queries(kg: Graph, ent: np.ndarray, rel: np.ndarray,
                 n_queries: int, dataset: str, query_noise: float = 0.25,
                 seed: int = 1) -> list[Question]:
    """Questions as random relation chains from a topic entity, with the
    dataset's hop mix; the query embedding is the chain's signature plus
    noise."""
    rng = np.random.default_rng(seed)
    mix = HOP_MIX[dataset]
    hop_choices = np.asarray(list(mix.keys()))
    hop_probs = np.asarray(list(mix.values()))
    hop_probs = hop_probs / hop_probs.sum()
    questions: list[Question] = []
    attempts = 0
    while len(questions) < n_queries and attempts < n_queries * 20:
        attempts += 1
        hops = int(rng.choice(hop_choices, p=hop_probs))
        topic = int(rng.integers(0, kg.n_entities))
        node, chain = topic, []
        for _ in range(hops):
            edges = kg.out_edges(node)
            if len(edges) == 0:
                break
            ei = int(edges[rng.integers(0, len(edges))])
            chain.append(ei)
            node = int(kg.tails[ei])
        if len(chain) < hops:
            continue
        sig = ent[topic] + rel[kg.rels[chain]].sum(0)
        q_emb = (sig + rng.normal(0, query_noise, sig.shape)).astype(np.float32)
        questions.append(Question(topic=topic, query_emb=q_emb,
                                  gold_edges=np.asarray(chain, np.int32),
                                  answer=node, hops=hops))
    return questions
