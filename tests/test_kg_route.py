"""Questions over a knowledge graph held on the device
(`repro.retrieval.store.DeviceKG`, `SkewRouteSession.submit_questions`)
against the plain reference: NumPy features over each question's own
subgraph (`repro.retrieval.store.question_features`) fed to the existing
fused program (`repro.core.router.route_retrieved`), on seeded random
weights at 64-wide embeddings and hidden layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import RouteSpec, build
from repro.core import router
from repro.retrieval.scorer import MAX_DDE_HOPS, ScorerConfig, init_scorer
from repro.retrieval.store import (DeviceKG, question_features,
                                   subgraph_distances)
from repro.serving.router_service import QuestionRecord

D = 64
MAX_CANDS = 512
TOP_K = 100
N_ENT, N_REL, N_TRIPLES = 400, 30, 6000
#: Logits: both sides sum the same float32 products (at most Dt = 206 in
#: the first layer, H = 64 in the second) in another order, and the
#: similarities' dots of D = 64 terms likewise; each sum's rounding is a
#: few float32 ulps of terms of size about 1, so 1e-5 bounds the gap with
#: room while a wrong feature moves a logit by far more.
LOGIT_ATOL = 1e-5


@pytest.fixture(scope="module")
def kg():
    rng = np.random.default_rng(7)
    ent = rng.normal(0, 1, (N_ENT, D)).astype(np.float32)
    rel = rng.normal(0, 1, (N_REL, D)).astype(np.float32)
    heads = rng.integers(0, N_ENT, N_TRIPLES).astype(np.int32)
    rels = rng.integers(0, N_REL, N_TRIPLES).astype(np.int32)
    tails = rng.integers(0, N_ENT, N_TRIPLES).astype(np.int32)
    store = DeviceKG.put(ent, rel, heads, rels, tails, max_cands=MAX_CANDS)
    params = init_scorer(jax.random.key(3),
                         ScorerConfig(d_emb=D, d_hidden=D))
    # nonzero biases, so that the reference is held to every weight
    params = dict(params, b1=jnp.full((D,), 0.05), b2=jnp.full((1,), -0.2))
    return store, (ent, rel, heads, rels, tails), params


def _questions(rng, host, b, counts):
    """``b`` questions: topics, queries, [b, MAX_CANDS] candidate ids that
    hold each topic's own out-edges first (so the subgraph has hops), and
    the given counts, cycled."""
    _, _, heads, _, tails = host
    topics = rng.integers(0, N_ENT, b).astype(np.int32)
    cand = np.zeros((b, MAX_CANDS), np.int32)
    for i, t in enumerate(topics):
        out1 = np.flatnonzero(heads == t)
        own = rng.permutation(np.union1d(
            out1, np.flatnonzero(np.isin(heads, tails[out1]))))
        rest = rng.permutation(N_TRIPLES)
        cand[i] = np.concatenate([own, rest[~np.isin(rest, own)]])[:MAX_CANDS]
    n_cand = np.resize(np.asarray(counts, np.int32), b)
    query = rng.normal(0, 1, (b, D)).astype(np.float32)
    return topics, query, cand, n_cand


def _reference(host, params, config, topics, query, cand, n_cand):
    """Features of the plain reference through the existing fused
    program: (triple ids, probs, n_valid, tiers, difficulty)."""
    b = len(topics)
    dt = ScorerConfig(d_emb=D).d_triple
    feats = np.zeros((b, MAX_CANDS, dt), np.float32)
    for i in range(b):
        feats[i, :n_cand[i]] = question_features(
            *host, topics[i], query[i], cand[i, :n_cand[i]])
    res = router.route_retrieved(jnp.asarray(feats), jnp.asarray(query),
                                 params, config, n_cand=jnp.asarray(n_cand),
                                 use_kernels=False)
    nv = np.asarray(res.n_valid)
    ids = np.take_along_axis(cand, np.asarray(res.indices), axis=1)
    ids = np.where(np.arange(ids.shape[1])[None, :] < nv[:, None], ids, -1)
    return ids, np.asarray(res.probs), nv, np.asarray(res.tiers), \
        np.asarray(res.difficulty), feats


def _session(store, params, thresholds=(-0.45,)):
    handed = {0: [], 1: []}

    def runner(tier):
        return lambda batch: handed[tier].extend(batch)
    spec = RouteSpec(metric="gini", thresholds=thresholds, top_k=TOP_K,
                     tier_names=("small", "large"), micro_batch=4)
    return build(spec, runners={t: runner(t) for t in handed}, store=store,
                 scorer=params), handed


@pytest.mark.parametrize("b", [5, 40])  # padded to buckets 8 and 64
def test_submit_questions_matches_plain_reference(kg, b):
    store, host, params = kg
    rng = np.random.default_rng(100 + b)
    topics, query, cand, n_cand = _questions(rng, host, b,
                                             [1, 100, 512, 37, 250])
    session, _ = _session(store, params)
    # a threshold midway between the middle difficulties, so both tiers
    # are taken and no difficulty sits near it
    mid = np.sort(session.submit_questions(
        topics, query, cand, n_cand).result.difficulty)[b // 2 - 1:b // 2 + 1]
    session.dispatcher.apply_config(dataclasses.replace(
        session.dispatcher.router, thresholds=(float(mid.mean()),)))
    res = session.submit_questions(topics, query, cand, n_cand)
    ids, probs, nv, tiers, diff, feats = _reference(
        host, params, session.dispatcher.router, topics, query, cand, n_cand)
    np.testing.assert_array_equal(res.n_valid, np.minimum(n_cand, TOP_K))
    np.testing.assert_array_equal(res.n_valid, nv)
    np.testing.assert_array_equal(res.triple_ids, ids)
    np.testing.assert_array_equal(res.tiers, tiers)
    np.testing.assert_allclose(res.probs, probs, rtol=0, atol=LOGIT_ATOL)
    np.testing.assert_allclose(res.result.difficulty, diff, rtol=0,
                               atol=1e-5)
    assert set(res.tiers.tolist()) == {0, 1}   # both tiers exercised

    # the program's feature parts, concatenated here, and its logits from
    # the parts on both sides, beside the reference's
    dev = [jnp.asarray(x) for x in (topics, query, cand, n_cand)]
    h, r, t, hops = (np.asarray(x) for x in jax.jit(router.kg_parts)(
        store.ent, store.rel, store.heads, store.rels, store.tails, dev[0],
        dev[2], dev[3]))
    eye = np.eye(MAX_DDE_HOPS + 1, dtype=np.float32)
    qv = query / np.sqrt(np.float32(D))
    sims = np.stack([np.einsum("bnd,bd->bn", x, qv)
                     for x in (h, r, t, h + r)], axis=-1)
    got = np.concatenate([h, r, t, eye[hops[..., 0]], eye[hops[..., 1]],
                          sims], axis=-1)
    mask = np.arange(MAX_CANDS)[None, :] < n_cand[:, None]
    np.testing.assert_allclose(got[mask], feats[mask], rtol=0, atol=1e-5)
    w = {k: np.asarray(v, np.float64) for k, v in params.items()}

    def logits(f):
        h = np.maximum(f @ w["w1_t"] + (query.astype(np.float64)
                                        @ w["w1_q"])[:, None] + w["b1"], 0)
        return (h @ w["w2"])[..., 0] + w["b2"][0]
    want = logits(feats.astype(np.float64))[mask]
    for use_kernels in (False, True):
        lg = np.asarray(router.score_parts(
            *(jnp.asarray(x) for x in (h, r, t, hops)), dev[1],
            *(params[k] for k in ("w1_t", "w1_q", "b1", "w2", "b2")),
            use_kernels=use_kernels, interpret=True, tile=128,
            n_cand=dev[3]))
        np.testing.assert_allclose(lg[mask], want, rtol=0, atol=LOGIT_ATOL)


#: hand-built subgraphs: (topic, [(head, tail)], padded (head, tail) slots)
SUBGRAPHS = {
    "cycle": (0, [(0, 1), (1, 2), (2, 0), (2, 3)], []),
    "unreachable_head": (0, [(0, 1), (5, 6), (6, 7), (1, 2)], []),
    "topic_as_tail": (3, [(1, 3), (3, 4), (4, 3), (2, 1)], []),
    "duplicate_endpoints": (0, [(0, 1), (0, 1), (1, 1), (1, 2), (2, 2),
                                (0, 2)], []),
    "beyond_max_hops": (0, [(i, i + 1) for i in range(7)], []),
    "padded_slots_are_no_edges": (0, [(0, 1), (2, 3)], [(1, 2), (0, 3)]),
}


@pytest.mark.parametrize("name", SUBGRAPHS)
def test_hop_distances_match_bfs(name):
    topic, edges, padded = SUBGRAPHS[name]
    heads = np.asarray([h for h, _ in edges + padded], np.int32)
    tails = np.asarray([t for _, t in edges + padded], np.int32)
    valid = np.arange(len(heads)) < len(edges)
    dh, dt = jax.jit(router.subgraph_hops, static_argnums=4)(
        jnp.asarray([topic]), jnp.asarray(heads[None]),
        jnp.asarray(tails[None]), jnp.asarray(valid[None]), MAX_DDE_HOPS)
    dist = subgraph_distances(topic, heads[valid], tails[valid])

    def bucket(x):
        return min(dist.get(int(x), MAX_DDE_HOPS), MAX_DDE_HOPS)
    n = len(edges)
    assert np.asarray(dh)[0, :n].tolist() == [bucket(h) for h in heads[:n]]
    assert np.asarray(dt)[0, :n].tolist() == [bucket(t) for t in tails[:n]]


def test_each_runner_receives_the_question_and_its_top_k_ids(kg):
    store, host, params = kg
    rng = np.random.default_rng(5)
    topics, query, cand, n_cand = _questions(rng, host, 11, [3, 100, 512])
    session, handed = _session(store, params)
    payloads = [f"q{i}" for i in range(11)]
    res = session.submit_questions(topics, query, cand, n_cand,
                                   payloads=payloads)
    session.flush()
    got = {r.request_id: (tier, r) for tier, recs in handed.items()
           for r in recs}
    assert sorted(got) == list(range(res.result.first_id,
                                     res.result.first_id + 11))
    for i in range(11):
        tier, rec = got[res.result.first_id + i]
        assert isinstance(rec, QuestionRecord)
        assert tier == rec.tier == res.tiers[i]
        assert rec.payload == payloads[i]
        np.testing.assert_array_equal(
            rec.triple_ids, res.triple_ids[i, :res.n_valid[i]])
        assert set(rec.triple_ids.tolist()) <= set(cand[i, :n_cand[i]].tolist())


def test_one_compilation_per_bucket_for_mixed_candidate_counts(kg):
    _, host, params = kg
    # a store of its own shape, so that no other test compiled for it
    store = DeviceKG.put(*host[:2], *(a[:-1] for a in host[2:]),
                         max_cands=MAX_CANDS)
    rng = np.random.default_rng(9)
    session, _ = _session(store, params, thresholds=(-0.3,))
    before = router._kg_program._cache_size()
    for b, width, counts in [(3, 512, [512, 7, 1]), (8, 40, [40, 2]),
                             (2, 1, [1]), (6, 300, [300, 299, 5])]:
        topics, query, cand, _ = _questions(rng, host, b, [1])
        n_cand = np.resize(np.asarray(counts, np.int32), b)
        session.submit_questions(topics, query,
                                 cand[:, :width] % store.n_triples, n_cand)
    assert router._kg_program._cache_size() == before + 1   # bucket 8 only
    topics, query, cand, _ = _questions(rng, host, 9, [1])
    session.submit_questions(topics, query, cand % store.n_triples,
                             np.full(9, 512))
    assert router._kg_program._cache_size() == before + 2   # and bucket 64


def test_submit_questions_refuses_what_it_cannot_route(kg):
    store, host, params = kg
    rng = np.random.default_rng(11)
    topics, query, cand, _ = _questions(rng, host, 2, [1])
    session, _ = _session(store, params)
    for n_cand in ([0, 5], [5, 513]):
        with pytest.raises(ValueError, match="candidate counts"):
            session.submit_questions(topics, query, cand, n_cand)
    for bad_ids, bad_topics in ((cand - N_TRIPLES, topics),
                                (cand + N_TRIPLES, topics),
                                (cand, topics + N_ENT)):
        with pytest.raises(ValueError, match="must lie in"):
            session.submit_questions(bad_topics, query, bad_ids, [3, 3])
    wide = np.zeros((2, MAX_CANDS + 1), np.int32)
    with pytest.raises(ValueError, match="slots"):
        session.submit_questions(topics, query, wide, [1, 1])
    with pytest.raises(RuntimeError, match="store="):
        build(RouteSpec(), runners={0: list, 1: list}).submit_questions(
            topics, query, cand, [1, 1])


def test_depth_routing_ships_each_question_at_its_depth(kg):
    """Under the adaptive-depth policy a question's valid prefix, scores,
    triple ids and hand-off are cut to its routed depth."""
    from repro.api import AdaptiveDepthPolicySpec
    store, host, params = kg
    rng = np.random.default_rng(13)
    topics, query, cand, n_cand = _questions(rng, host, 12, [512, 60, 5])
    handed = []
    spec = RouteSpec(metric="gini", thresholds=(-0.45,), top_k=TOP_K,
                     tier_names=("small", "large"), micro_batch=1,
                     policy=AdaptiveDepthPolicySpec(depth_options=(10, 50),
                                                    depth_cutoffs=(-0.45,)))
    session = build(spec, runners={0: handed.extend, 1: handed.extend},
                    store=store, scorer=params)
    res = session.submit_questions(topics, query, cand, n_cand)
    depths = np.asarray(res.result.depths)
    assert set(depths.tolist()) == {10, 50}
    np.testing.assert_array_equal(res.n_valid,
                                  np.minimum(np.minimum(n_cand, TOP_K), depths))
    past = np.arange(TOP_K)[None, :] >= res.n_valid[:, None]
    assert (res.triple_ids[past] == -1).all() and (res.probs[past] == 0).all()
    assert (res.triple_ids[~past] >= 0).all()
    assert sorted(len(r.triple_ids) for r in handed) == sorted(res.n_valid)


class _InterpretedKernels:
    """A backend whose question program runs the Pallas kernels under the
    interpreter: the built-in backends trace the XLA ops off the chip, so
    only this one drives the ragged scorer through `dispatch_kg` here."""

    name = "interpreted-kernels"

    def route_kg(self, store, params, config, *args):
        return router.route_kg(store, params, config, *args, interpret=True,
                               use_kernels=True)


@pytest.mark.parametrize("b", [9, 37, 64])  # bucket 64: 55, 27, 0 pad rows
def test_ragged_scorer_matches_xla_path_and_reference(kg, b):
    """Through `dispatch_kg`, the scorer kernel that skips each row's tiles
    past its count, and every tile of the bucket's padding rows, gives the
    XLA path's and the reference's tiers and top-K triple ids."""
    store, host, params = kg
    rng = np.random.default_rng(200 + b)
    topics, query, cand, n_cand = _questions(
        rng, host, b, [512, 1, 127, 128, 129, 300, 37])
    xla, _ = _session(store, params)
    mid = np.sort(xla.submit_questions(
        topics, query, cand, n_cand).result.difficulty)[b // 2 - 1:b // 2 + 1]
    config = dataclasses.replace(xla.dispatcher.router,
                                 thresholds=(float(mid.mean()),))
    kernels, _ = _session(store, params)
    kernels.dispatcher.backend = _InterpretedKernels()
    for session in (xla, kernels):
        session.dispatcher.apply_config(config)
    want = xla.submit_questions(topics, query, cand, n_cand)
    got = kernels.submit_questions(topics, query, cand, n_cand)
    ids, probs, nv, tiers, _, _ = _reference(host, params, config, topics,
                                             query, cand, n_cand)
    for res in (want, got):
        np.testing.assert_array_equal(res.n_valid, nv)
        np.testing.assert_array_equal(res.triple_ids, ids)
        np.testing.assert_array_equal(res.tiers, tiers)
        np.testing.assert_allclose(res.probs, probs, rtol=0, atol=LOGIT_ATOL)
    assert set(got.tiers.tolist()) == {0, 1}
