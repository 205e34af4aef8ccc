"""The benchmark's parts for questions over a knowledge graph held on the
chip: the reference's subgraph distances on hand-built subgraphs, the
work counts against hand counts, the CPU cut, and the readers of the
cell's per-layer metrics."""

import types

import numpy as np
import pytest

from perfbench import reference, reference_kg, work, work_kg
from perfbench.tests import cells

#: (topic, [(head, tail)], {node: distance bucket})
SUBGRAPHS = {
    "cycle": (0, [(0, 1), (1, 2), (2, 0), (2, 3)], {0: 0, 1: 1, 2: 2, 3: 3}),
    "unreachable": (0, [(0, 1), (5, 6)], {0: 0, 1: 1, 5: 4, 6: 4}),
    "topic_as_tail": (3, [(1, 3), (3, 4)], {1: 4, 3: 0, 4: 1}),
    "duplicates": (0, [(0, 1), (0, 1), (1, 1)], {0: 0, 1: 1}),
    "long_chain": (0, [(i, i + 1) for i in range(6)],
                   {0: 0, 1: 1, 2: 2, 3: 3, 4: 4, 5: 4, 6: 4}),
}


@pytest.mark.parametrize("name", SUBGRAPHS)
def test_subgraph_distance_by_hand(name):
    topic, edges, want = SUBGRAPHS[name]
    heads = np.asarray([h for h, _ in edges] + [9], np.int32)
    tails = np.asarray([t for _, t in edges] + [topic], np.int32)
    g = reference.Graph(heads, np.zeros_like(heads), tails, 10, 1)
    # the last triple (9 -> topic) is no candidate: it adds no edge
    dist = reference_kg.subgraph_distance(g, topic, np.arange(len(edges)))
    got = {n: min(dist.get(n, reference.MAX_HOPS), reference.MAX_HOPS)
           for n in want}
    assert got == want


def test_features_take_the_subgraph_distance():
    g = reference.Graph(np.asarray([0, 1, 2]), np.asarray([0, 0, 0]),
                        np.asarray([1, 2, 0]), 3, 1)
    ent = np.eye(3)
    rel = np.ones((1, 3))
    f = reference_kg.features(g, ent, rel, 0, np.ones(3), np.asarray([1, 2]))
    hop = reference.MAX_HOPS + 1
    # without triple 0 (0 -> 1) in the subgraph, 1 and 2 are unreachable
    # and the topic is reached from 2 in one hop: tail of triple 2 is 0
    assert f.shape == (2, 9 + 2 * hop + 4)
    assert f[:, 9:9 + hop].argmax(1).tolist() == [4, 4]
    assert f[:, 9 + hop:9 + 2 * hop].argmax(1).tolist() == [4, 0]


def test_question_work_hand_count():
    d, h, k = 2, 3, 2
    dt = work_kg.d_triple(d)
    assert dt == 3 * d + 2 * (reference.MAX_HOPS + 1) + 4
    flops, nbytes = work_kg.question_work([np.asarray([3, 1])], d, h, k)
    assert flops == (work.scorer_flops([3, 1], dt, d, h)
                     + 4 * work_kg.SIM_FLOPS_PER_ELEMENT * d
                     + work.DECISION_FLOPS_PER_SCORE * (2 + 1))
    weights = 4 * (dt * h + d * h + 2 * h + 1)
    per_cand = 4 * (4 + 3 * d)          # 4 ids, 3 rows of d
    per_question = 4 * (2 + d + 2 * k) + work.DECISION_OUT_BYTES
    assert nbytes == weights + 4 * per_cand + 2 * per_question
    # each call reads the weights once
    two = work_kg.question_work([np.asarray([3]), np.asarray([1])], d, h, k)
    assert two[1] == nbytes + weights


def test_kg_cell_is_cut_as_the_retrieve_cells():
    cfg, mix = cells.load("subgraphrag-gte1024-kg", "online")
    cfg_cut, mix_cut = cells.cut(cfg, mix)
    assert cfg_cut == {**cfg, "d_emb": 64, "d_hidden": 64,
                       "n_entities": 2000, "calibration_questions": 8,
                       "check_requests": 12}
    assert mix_cut == {**mix, "pool": 32, "rate": 10}
    assert cells.hands_off(cfg)


def test_kg_readers_read_nothing_without_their_inputs():
    from perfbench import harness
    ctx = types.SimpleNamespace(trace=None, peaks=None, dep=object(),
                                config={}, window=None, mix={})
    for name in ("kg_roofline", "dispatch_host_ms.kg",
                 "device_idle_share.kg", "scorer_mfu.latency"):
        assert harness.find_reader(name)(ctx) is None
