"""The chunked tail search of `datagen.make_kg` makes the graph and tables
of one search over all edges, bit for bit."""

import numpy as np
import pytest

from perfbench import datagen
from perfbench.reference import Graph


def make_kg_unchunked(n_entities, n_relations, avg_degree, structure_dim,
                      width, seed):
    """`datagen.make_kg` as it was before its tail search was chunked:
    the ``[n_edges, 16, structure_dim]`` search in one piece."""
    rng = np.random.default_rng(seed)
    ent = rng.normal(0, 1, (n_entities, structure_dim)).astype(np.float32)
    rel = rng.normal(0, 1, (n_relations, structure_dim)).astype(np.float32)
    deg = np.minimum(rng.zipf(1.7, n_entities), 200)
    deg = np.maximum((deg * avg_degree / deg.mean()).astype(np.int64), 1)
    n_edges = int(deg.sum())
    heads = np.repeat(np.arange(n_entities, dtype=np.int32), deg)
    rels = rng.integers(0, n_relations, n_edges).astype(np.int32)
    pool = rng.integers(0, n_entities, (n_edges, 16))
    target = ent[heads] + rel[rels] + rng.normal(0, 0.3,
                                                 (n_edges, structure_dim))
    dists = np.linalg.norm(ent[pool] - target[:, None, :], axis=-1)
    tails = pool[np.arange(n_edges), dists.argmin(1)].astype(np.int32)
    proj = rng.normal(0, structure_dim ** -0.5,
                      (structure_dim, width)).astype(np.float32)
    return (Graph(heads, rels, tails, n_entities, n_relations),
            ent @ proj, rel @ proj)


# (n_entities, n_relations, avg_degree, structure_dim, width, seed, chunk):
# every edge alone; the CPU tests' retrieve graph in chunks that do not
# divide its edges; a larger graph in chunks that do not either
SIZES = [
    (300, 7, 3.0, 8, 16, 5, 1),
    (2000, 200, 8.0, 32, 64, 0, 97),
    (6000, 50, 8.0, 32, 96, 2 ** 33 + 3, 4096),
]


@pytest.mark.parametrize("n_entities,n_relations,avg_degree,structure_dim,"
                         "width,seed,chunk", SIZES)
def test_chunked_make_kg_is_bit_identical(n_entities, n_relations,
                                          avg_degree, structure_dim, width,
                                          seed, chunk, monkeypatch):
    monkeypatch.setattr(datagen, "CHUNK", chunk)
    args = (n_entities, n_relations, avg_degree, structure_dim, width, seed)
    want_g, want_ent, want_rel = make_kg_unchunked(*args)
    got_g, got_ent, got_rel = datagen.make_kg(*args)
    n_edges = len(want_g.heads)
    assert chunk == 1 or n_edges % chunk, "the last chunk must be partial"
    for name in ("heads", "rels", "tails"):
        want, got = getattr(want_g, name), getattr(got_g, name)
        assert got.dtype == want.dtype and np.array_equal(got, want), name
    for want, got in ((want_ent, got_ent), (want_rel, got_rel)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
