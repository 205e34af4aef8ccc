"""Knowledge-graph-store deployments: the router holds the knowledge graph
on the chip (`repro.retrieval.store.DeviceKG`) and each question arrives
as its topic entity, its query embedding and the ids of its candidate
triples (its retrieved subgraph). `SkewRouteSession.submit_questions`
gathers the features from the on-chip tables, scores them with the
triple scorer, keeps the top K, decides, and hands each question with
its top-K triple ids to its tier's runner, as one device program and the
pipeline's hand-off.

The graph, the question pool and each question's candidate ids
(`perfbench.reference.candidates`) are the deployment's data, made from
the configuration's ``data_seed``. The run's seed draws the scorer
weights, the order in which pool questions arrive, the calibration
sample and the sample that is checked. The tier runners record what they
receive, (request id, top-K triple ids): the tier LLMs live outside the
router's process.

The check holds a sample of the served questions to the float64
reference (`perfbench.reference_kg` features, `perfbench.reference` for
the rest), and every served question to its hand-off: one runner, the
tier and the triple ids the call returned.
"""

from __future__ import annotations

import numpy as np

from perfbench import datagen, reference, reference_kg, traffic
from perfbench.paths import retrieve

#: the same cut as the retrieve kind: 64-wide embeddings and hidden layer
#: over 2,000 entities, 8 calibration and 12 checked questions, a pool of
#: 32 and, in an open loop, 10 arrivals a second
small = retrieve.small


class Deployment(retrieve.Deployment):
    #: ``outputs`` entries: (first request, n_cand, top-K triple ids,
    #: probs, n_valid, tiers, difficulty, metrics, first session id)
    tiers_column = 5
    hands_off = True

    def __init__(self, config: dict, mix: dict, seed: int, spans):
        super().__init__(config, mix, seed, spans)
        #: per tier, (session request id, top-K triple ids) as received
        self.handed: list[list] = [[] for _ in config["tier_shares"]]

    def _handoff(self, tier: int):
        got = self.handed[tier]

        def run(batch):
            got.extend((r.request_id, r.triple_ids) for r in batch)
        return run

    def setup(self) -> None:
        # first, so that a program without the store fails at once
        from repro.retrieval.store import DeviceKG
        import jax.numpy as jnp
        from repro.api import RouteSpec, build
        from repro.core.calibrate import calibrate_multi_tier
        from repro.serving.router_service import BATCH_BUCKETS

        c = self.config
        data_seed, max_cands = int(c["data_seed"]), int(c["max_cands"])
        self.graph, self.ent, self.rel = datagen.make_kg(
            int(c["n_entities"]), int(c["n_relations"]),
            float(c["avg_degree"]), int(c["structure_dim"]), int(c["d_emb"]),
            seed=data_seed)
        g = self.graph
        pool = datagen.make_queries(g, self.ent, self.rel,
                                    int(self.mix["pool"]), c["dataset"],
                                    seed=data_seed + 1)
        self.topics = np.asarray([q.topic for q in pool], np.int32)
        self.qemb = np.stack([q.query_emb for q in pool]).astype(np.float32)
        self.cand = np.zeros((len(pool), max_cands), np.int32)
        self.n_cand = np.zeros(len(pool), np.int32)
        for i, q in enumerate(pool):
            ids = reference.candidates(g, q.topic, q.hops, q.gold_edges,
                                       max_cands, int(c["candidate_seed"]))
            self.cand[i, :len(ids)] = ids
            self.n_cand[i] = len(ids)
        self.params = retrieve.init_weights(self.seed, *self.widths)
        store = DeviceKG.put(self.ent, self.rel, g.heads, g.rels, g.tails,
                             max_cands=max_cands)
        self.session = build(RouteSpec(
            metric=c["metric"], thresholds=(0.0,) * (len(c["tier_shares"]) - 1),
            cumulative_p=c["cumulative_p"], top_k=self.k,
            tier_names=tuple(c["tier_names"]), backend=c["backend"],
            crossover_batch=c["crossover_batch"],
            micro_batch=c["micro_batch"]),
            runners={t: self._handoff(t) for t in range(len(self.handed))},
            store=store, scorer=self.params)

        def submit(qids):
            return self.session.submit_questions(
                self.topics[qids], self.qemb[qids], self.cand[qids],
                self.n_cand[qids])

        # thresholds from a seeded sample of the pool, in calls that fill
        # the largest bucket this mix uses
        sizes = traffic.batch_sizes(self.mix, BATCH_BUCKETS)
        gen = traffic.rng(self.seed, traffic.STREAM_CALIBRATION)
        self.cal_ids = gen.choice(len(pool), int(c["calibration_questions"]),
                                  replace=False)
        step = min(b for b in BATCH_BUCKETS if b >= sizes[-1][1])
        probs, n_valid = [], []
        for s in range(0, len(self.cal_ids), step):
            res = submit(self.cal_ids[s:s + step])
            probs.append(res.probs)
            n_valid.append(res.n_valid)
        probs, n_valid = np.concatenate(probs), np.concatenate(n_valid)
        mask = np.arange(probs.shape[1])[None, :] < n_valid[:, None]
        self.session.dispatcher.apply_config(calibrate_multi_tier(
            jnp.asarray(probs), c["tier_shares"], metric=c["metric"],
            cumulative_p=c["cumulative_p"], mask=jnp.asarray(mask)))
        # the smallest and largest batch of every bucket the mix's calls
        # land in, twice each, under the calibrated thresholds
        for lo, hi in sizes:
            for _ in range(2):
                for b in (lo, hi):
                    submit(np.arange(b) % len(pool))
        self.session.flush()
        self.order = traffic.rng(self.seed, traffic.STREAM_ORDER).integers(
            0, len(pool), 1 << 16)
        self.outputs = []
        for got in self.handed:
            got.clear()

    def serve(self, i: int, j: int) -> None:
        q = self.question_ids(i, j)
        topics, qemb, cand, n_cand = (self.topics[q], self.qemb[q],
                                      self.cand[q], self.n_cand[q])
        with self.spans.span("session_call", j - i):
            res = self.session.submit_questions(topics, qemb, cand, n_cand)
        r = res.result
        self.outputs.append((i, n_cand, res.triple_ids, res.probs,
                             res.n_valid, r.tiers, r.difficulty, r.metrics,
                             r.first_id))

    def release(self) -> None:
        self.session.flush()
        super().release()

    # -- correctness --------------------------------------------------------

    def _ref_logits(self, qids, matmul=None) -> dict:
        """Reference logits of each pool question in ``qids``: float64, or
        float32 features through ``matmul``."""
        w = self.weights
        out = {}
        for q in sorted(set(int(x) for x in qids)):
            feats = reference_kg.features(
                self.graph, self.ent, self.rel, int(self.topics[q]),
                self.qemb[q], self.cand[q, :self.n_cand[q]])
            if matmul is None:
                out[q] = reference.mlp_logits(feats, self.qemb[q], w)
            else:
                out[q] = reference.mlp_logits(feats.astype(np.float32),
                                              self.qemb[q], w, matmul)
        return out

    def _positions(self, qids, triple_ids) -> np.ndarray:
        """Each served triple id's position in its question's candidate
        list (-1: not a candidate of it)."""
        pos = np.full(np.shape(triple_ids), -1, np.int64)
        for r, q in enumerate(qids):
            at = {e: p for p, e in
                  enumerate(self.cand[q, :self.n_cand[q]].tolist())}
            pos[r] = [at.get(e, -1) for e in np.asarray(triple_ids[r]).tolist()]
        return pos

    def _handoff_numbers(self, window, numbers: dict) -> dict:
        """Every request served for the window against what the runners
        received: one that no runner or more than one received is
        ``missing``; a runner's tier or triple ids other than the call
        returned make ``decision_err`` or ``score_err`` infinite."""
        received: dict[int, list] = {}
        for tier, got in enumerate(self.handed):
            for rid, ids in got:
                received.setdefault(int(rid), []).append((tier, ids))
        lost = 0
        for o in self.outputs:
            for r in range(len(o[5])):
                if o[0] + r >= window.n_due:
                    continue
                got = received.get(o[8] + r, [])
                if len(got) != 1:
                    lost += 1
                    continue
                tier, ids = got[0]
                if tier != int(o[5][r]):
                    numbers["decision_err"] = float("inf")
                if not np.array_equal(ids, o[2][r, :int(o[4][r])]):
                    numbers["score_err"] = float("inf")
        numbers["missing"] += lost
        return numbers

    def check(self, window) -> dict:
        ids, got = self._sample(window)
        if not len(ids):
            return {"metric_err": 0.0, "cdf_gap": 0.0, "decision_err": 0.0,
                    "score_err": 0.0, "missing": window.n_due}
        qids = self.order[ids % len(self.order)]
        got["indices"] = self._positions(qids, got["indices"])
        logits = self._ref_logits(np.concatenate([qids, self.cal_ids]))
        return self._handoff_numbers(
            window, self._numbers(window, qids, got, logits))
