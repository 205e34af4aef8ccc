"""One host-device round trip per dispatch: the dispatcher moves a batch's
inputs in one transfer, launches one program and reads its results back
in one ``jax.device_get``.

Checked on both sides of ``auto``'s crossover (B=5 pads to bucket 8, the
XLA oracle; B=40 pads to bucket 64, the ``skew_metrics`` kernel, in
interpret mode off-TPU), with dense and ragged ``n_valid``:

* the served tiers, difficulty and metrics are bit-for-bit those of the
  decision math compiled as a program of three outputs, run on the same
  padded rows and read back one array at a time;
* a warmed dispatch runs under a transfer guard that refuses implicit
  copies in either direction;
* a profiler trace of one warmed dispatch holds one program launch, the
  decision program, and no ``atleast_2d`` program.

The end-to-end ``dispatch_retrieved`` path gets the same checks against
``core.router.route_retrieved``; a warmed question dispatch
(``dispatch_kg``) launches only its program too. All three entries pad a
batch to its bucket alike: score rows with one valid score, candidate
rows with none, and nothing downstream sees a padding row.
"""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import RouteSpec, build, make_backend
from repro.core import router
from repro.core.router import route_retrieved
from repro.kernels.device import default_interpret
from repro.obs import ManualClock, Observability
from repro.retrieval.store import DeviceKG
from repro.serving.router_service import BATCH_BUCKETS
from repro.serving.scheduler import bucket_size

K = 100
CROSSOVER = 32
D_TRIPLE, D_QUERY, D_HIDDEN, N_CAND = 12, 8, 16, 64
#: The question path's graph: entities, relations, triples and each
#: question's candidate slots; embeddings are D_QUERY wide.
N_ENT, N_REL, N_TRIPLES, MAX_CANDS = 50, 5, 400, 32

#: (batch, the side of auto's crossover its padded bucket lands on)
SIDES = [(5, "oracle"), (40, "fused")]

#: Two thresholds per metric, near its quartiles on these rows, so that
#: every batch spreads over three tiers.
THRESHOLDS = {"area": (9.0, 25.0), "cumulative": (18.0, 54.0),
              "entropy": (4.2, 5.8), "gini": (-0.4, -0.1)}


def _session(obs=None, metric="gini", **kg):
    spec = RouteSpec(metric=metric, thresholds=THRESHOLDS[metric], top_k=K,
                     tier_names=("small", "medium", "large"),
                     crossover_batch=CROSSOVER, micro_batch=8)
    return build(spec, runners={t: (lambda batch: batch) for t in range(3)},
                 obs=obs or Observability(clock=ManualClock()), **kg)


def _rows(rng, b, ragged):
    scores = -np.sort(-rng.power(0.4, (b, K)).astype(np.float32), axis=1)
    nv = rng.integers(1, K + 1, b).astype(np.int32) if ragged else None
    return scores, nv


def _params(rng):
    shapes = (("w1_t", (D_TRIPLE, D_HIDDEN)), ("w1_q", (D_QUERY, D_HIDDEN)),
              ("b1", (D_HIDDEN,)), ("w2", (D_HIDDEN, 1)), ("b2", (1,)))
    return {name: jnp.asarray(rng.normal(0, 0.3, s).astype(np.float32))
            for name, s in shapes}


def _features(rng, b, ragged):
    feats = rng.normal(0, 1, (b, N_CAND, D_TRIPLE)).astype(np.float32)
    qemb = rng.normal(0, 1, (b, D_QUERY)).astype(np.float32)
    nc = (rng.integers(1, N_CAND + 1, b).astype(np.int32) if ragged
          else None)
    return feats, qemb, nc


def _kg(rng):
    """A seeded graph on the device and seeded scorer weights for it."""
    store = DeviceKG.put(
        rng.normal(0, 1, (N_ENT, D_QUERY)), rng.normal(0, 1, (N_REL, D_QUERY)),
        rng.integers(0, N_ENT, N_TRIPLES), rng.integers(0, N_REL, N_TRIPLES),
        rng.integers(0, N_ENT, N_TRIPLES), max_cands=MAX_CANDS)
    shapes = (("w1_t", (3 * D_QUERY + 14, D_HIDDEN)),
              ("w1_q", (D_QUERY, D_HIDDEN)), ("b1", (D_HIDDEN,)),
              ("w2", (D_HIDDEN, 1)), ("b2", (1,)))
    return store, {name: jnp.asarray(rng.normal(0, 0.3, s).astype(np.float32))
                   for name, s in shapes}


def _questions(rng, b):
    """``b`` questions: topics, queries, candidate ids and ragged counts."""
    return (rng.integers(0, N_ENT, b), rng.normal(0, 1, (b, D_QUERY)),
            rng.integers(0, N_TRIPLES, (b, MAX_CANDS)),
            rng.integers(1, MAX_CANDS + 1, b))


def _pad(x, bpad):
    pad = np.zeros((bpad - len(x),) + x.shape[1:], x.dtype)
    return np.concatenate([x, pad])


def _pad_counts(counts, b, bpad, full):
    out = np.full(bpad, full, np.int32)
    if counts is not None:
        out[:b] = counts
    out[b:] = 1
    return out


#: The decision math as a program of three outputs, as it was compiled
#: before the outputs were packed into one buffer.
_three_output_program = jax.jit(
    router._decide,
    static_argnames=("metric", "p_cdf", "ragged", "use_kernel", "interpret"))


def _reference_batch(session, scores, nv, side):
    """The three-output decision program on the dispatcher's padded rows,
    each result read with its own ``np.asarray``."""
    b = len(scores)
    bpad = bucket_size(b, BATCH_BUCKETS)
    cfg = session.dispatcher.router
    out = _three_output_program(
        jnp.asarray(_pad(scores, bpad)), jnp.asarray(cfg.thresholds),
        jnp.asarray(_pad_counts(nv, b, bpad, K)), metric=cfg.metric,
        p_cdf=cfg.cumulative_p, ragged=True, use_kernel=side == "fused",
        interpret=default_interpret())
    return tuple(np.asarray(a)[:b] for a in out)


def _reference_retrieved(session, params, feats, qemb, nc):
    b = len(feats)
    bpad = bucket_size(b, BATCH_BUCKETS)
    interp = default_interpret()
    res = route_retrieved(
        jnp.asarray(_pad(feats, bpad)), jnp.asarray(_pad(qemb, bpad)), params,
        session.dispatcher.router,
        n_cand=jnp.asarray(_pad_counts(nc, b, bpad, N_CAND)),
        interpret=interp, use_kernels=not interp)
    return [np.asarray(a)[:b] for a in (res.tiers, res.difficulty,
                                        res.metrics, res.n_valid, res.probs,
                                        res.indices)]


@pytest.mark.parametrize("metric", sorted(THRESHOLDS))
@pytest.mark.parametrize("ragged", [False, True], ids=["dense", "ragged"])
@pytest.mark.parametrize("b,side", SIDES)
def test_dispatch_batch_matches_the_decision_program_bit_for_bit(
        b, side, ragged, metric):
    rng = np.random.default_rng(b + 100 * ragged)
    obs = Observability(clock=ManualClock())
    session = _session(obs, metric)
    warm_scores, warm_nv = _rows(rng, b, ragged)
    session.submit(warm_scores, n_valid=warm_nv)
    scores, nv = _rows(rng, b, ragged)
    want = _reference_batch(session, scores, nv, side)
    with jax.transfer_guard("disallow"):
        got = session.dispatcher.dispatch_batch(scores, n_valid=nv,
                                                return_details=True)
    assert obs.metrics.value("backend_pick_total", path=side) == 2
    for g, w in zip((got.tiers, got.difficulty, got.metrics), want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("metric", sorted(THRESHOLDS))
@pytest.mark.parametrize("b", [b for b, _ in SIDES])
def test_route_batch_views_match_the_host_unpack(b, metric):
    """A backend's result holds one packed buffer; its device-side views
    read what the dispatcher's host unpack reads."""
    rng = np.random.default_rng(31 + b)
    session = _session(metric=metric)
    scores, nv = _rows(rng, b, True)
    res = session.backend.route_batch(jnp.asarray(scores),
                                      session.dispatcher.router,
                                      n_valid=jnp.asarray(nv))
    assert res.decision.shape == (b, router.N_METRICS + 1)
    host = router.unpack_decision(np.asarray(res.decision), metric)
    for view, h in zip((res.tiers, res.difficulty, res.metrics), host):
        assert view.dtype == h.dtype and view.shape == h.shape
        np.testing.assert_array_equal(np.asarray(view), h)


@pytest.mark.parametrize("ragged", [False, True], ids=["dense", "ragged"])
@pytest.mark.parametrize("b,side", [(3, "oracle"), (40, "fused")])
def test_dispatch_retrieved_matches_route_retrieved_bit_for_bit(
        b, side, ragged):
    rng = np.random.default_rng(7 + b + 100 * ragged)
    obs = Observability(clock=ManualClock())
    session = _session(obs)
    params = _params(rng)
    warm_feats, warm_qemb, warm_nc = _features(rng, b, ragged)
    session.route_retrieved(warm_feats, warm_qemb, params, n_cand=warm_nc)
    feats, qemb, nc = _features(rng, b, ragged)
    want = _reference_retrieved(session, params, feats, qemb, nc)
    with jax.transfer_guard("disallow"):
        got = session.route_retrieved(feats, qemb, params, n_cand=nc)
    assert obs.metrics.value("backend_pick_total", path=side) == 2
    r = got.result
    for g, w in zip((r.tiers, r.difficulty, r.metrics, got.n_valid,
                     got.probs, got.indices), want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def _launched_programs(trace_dir) -> list[str]:
    """Names of the jitted programs launched in the one trace under
    ``trace_dir`` (JAX's ``PjitFunction(<name>)`` host events)."""
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                        recursive=True)
    return [e.name
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("PjitFunction(")]


@pytest.mark.parametrize("path", ["batch_oracle", "batch_fused",
                                  "retrieved", "kg_oracle", "kg_fused"])
def test_a_warmed_dispatch_launches_only_its_decision_program(path,
                                                              tmp_path):
    rng = np.random.default_rng(23)
    session = _session()
    if path == "retrieved":
        params = _params(rng)
        feats, qemb, nc = _features(rng, 5, True)

        def call():
            session.route_retrieved(feats, qemb, params, n_cand=nc)
        program = "PjitFunction(_retrieved_program)"
    elif path.startswith("kg_"):
        store, params = _kg(rng)
        session = _session(store=store, scorer=params)
        # 5 questions pad to bucket 8 (oracle), 40 to bucket 64 (fused)
        questions = _questions(rng, 5 if path == "kg_oracle" else 40)

        def call():
            session.submit_questions(*questions)
        program = "PjitFunction(_kg_program)"
    else:
        scores, nv = _rows(rng, 5 if path == "batch_oracle" else 40, True)

        def call():
            session.submit(scores, n_valid=nv)
        program = "PjitFunction(_decision_program)"
    call()                                   # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        call()
    finally:
        jax.profiler.stop_trace()
    launched = _launched_programs(tmp_path)
    assert launched and set(launched) == {program}   # no atleast_2d


class _RecordingBackend:
    """The oracle backend behind every entry, keeping the host copy of the
    valid-score or candidate counts each entry receives."""

    name = "recording"

    def __init__(self):
        self.oracle = make_backend("oracle")
        self.counts = []

    def route_batch(self, scores, config, n_valid=None):
        self.counts.append(np.asarray(n_valid))
        return self.oracle.route_batch(scores, config, n_valid=n_valid)

    def route_retrieved(self, feats, query_emb, params, config, n_cand=None):
        self.counts.append(np.asarray(n_cand))
        return self.oracle.route_retrieved(feats, query_emb, params, config,
                                           n_cand=n_cand)

    def route_kg(self, store, params, config, topics, query_emb, cand_ids,
                 n_cand):
        self.counts.append(np.asarray(n_cand))
        return self.oracle.route_kg(store, params, config, topics, query_emb,
                                    cand_ids, n_cand)


@pytest.mark.parametrize("b", [5, 37])
@pytest.mark.parametrize("entry", ["dispatch_batch", "dispatch_retrieved",
                                   "dispatch_kg"])
def test_padding_rows_hold_one_score_or_no_candidates_and_stay_unseen(
        entry, b, monkeypatch):
    rng = np.random.default_rng(41 + b)
    d = _session().dispatcher
    d.backend = backend = _RecordingBackend()
    decided = []
    decide = d.policy.decide
    monkeypatch.setattr(d.policy, "decide", lambda tiers, diff, metrics, **kw:
                        decided.append((len(tiers), len(diff), len(metrics)))
                        or decide(tiers, diff, metrics, **kw))
    if entry == "dispatch_batch":
        scores, real = _rows(rng, b, True)
        res = d.dispatch_batch(scores, n_valid=real, return_details=True)
        pad = 1                      # one valid score: well-defined metrics
    elif entry == "dispatch_retrieved":
        feats, qemb, real = _features(rng, b, True)
        res = d.dispatch_retrieved(feats, qemb, _params(rng),
                                   n_cand=real).result
        pad = 0                      # no candidates: the scorer skips them
    else:
        store, params = _kg(rng)
        topics, qemb, cand, real = _questions(rng, b)
        res = d.dispatch_kg(store, params, topics, qemb, cand, real).result
        pad = 0
    (counts,) = backend.counts
    bpad = bucket_size(b, BATCH_BUCKETS)
    np.testing.assert_array_equal(
        counts, np.concatenate([real, np.full(bpad - b, pad)]))
    assert decided == [(b, b, b)]
    assert len(res.tiers) == len(res.difficulty) == len(res.metrics) == b
    assert d.stats.n_requests == sum(d.stats.tier_counts.values()) == b
