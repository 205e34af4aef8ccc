"""Retrieve deployments: the router gets each question and routes it from
its own retrieval. Per call, `repro.retrieval.scorer.batch_triple_features`
builds the candidate features on the host, and
`SkewRouteSession.route_retrieved` scores them with the triple scorer,
keeps the top K, and decides, as one device program.

The knowledge graph and the question pool are the deployment's data, made
from the configuration's ``data_seed``, so every run serves the same
candidate counts (and so the same programs). The run's seed draws the
scorer weights, the order in which pool questions arrive, the calibration
sample and the sample that is checked.
"""

from __future__ import annotations

import copy

import numpy as np

from perfbench import compare, datagen, reference, traffic


def init_weights(seed: int, d_triple: int, d_query: int, d_hidden: int):
    """Scorer weights, float32, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    def init(key):
        k = jax.random.split(key, 5)
        return {
            "w1_t": jax.random.normal(k[0], (d_triple, d_hidden)) * (2.0 / d_triple) ** 0.5,
            "w1_q": jax.random.normal(k[1], (d_query, d_hidden)) * (2.0 / d_query) ** 0.5,
            "b1": jax.random.normal(k[2], (d_hidden,)) * 0.1,
            "w2": jax.random.normal(k[3], (d_hidden, 1)) * (2.0 / d_hidden) ** 0.5,
            "b2": jax.random.normal(k[4], (1,)) * 0.1,
        }

    key = int(traffic.rng(seed, traffic.STREAM_WEIGHTS).integers(2 ** 31))
    return jax.jit(init)(jax.random.key(key))


def dot_high(a, b):
    """float32 matmul at JAX's ``high`` precision, spelled out: each operand
    split into two bfloat16 parts and three of the four products summed in
    float32. The same arithmetic on the chip and on the CPU."""
    import jax.numpy as jnp

    def split(x):
        x = jnp.asarray(x, jnp.float32)
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def dot(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def small(config: dict, mix: dict) -> tuple[dict, dict]:
    """Copies of a configuration and mix cut for the CPU tests: 64-wide
    embeddings and hidden layer over 2,000 entities, 8 calibration and 12
    checked questions, a pool of 32 and, in an open loop, 10 arrivals a
    second."""
    config, mix = copy.deepcopy((config, mix))
    config.update(d_emb=64, d_hidden=64, n_entities=2000,
                  calibration_questions=8, check_requests=12)
    mix["pool"] = 32
    if mix["loop"] == "open":
        mix["rate"] = 10
    return config, mix


class Deployment:
    #: where each entry of ``outputs`` holds the served tiers
    tiers_column = 5
    #: ``route_retrieved`` returns decisions and hands nothing to tiers
    hands_off = False

    def __init__(self, config: dict, mix: dict, seed: int, spans):
        self.config, self.mix, self.seed, self.spans = config, mix, seed, spans
        self.k = int(config["top_k"])
        self.max_batch = traffic.batch_limit(mix)
        self.outputs: list = []

    @property
    def widths(self) -> tuple[int, int, int]:
        """(triple features, query, hidden): 3 embeddings, the hop one-hots
        of head and tail, 4 similarities."""
        d = int(self.config["d_emb"])
        return (3 * d + 2 * (reference.MAX_HOPS + 1) + 4, d,
                int(self.config["d_hidden"]))

    def setup(self) -> None:
        import jax.numpy as jnp
        from repro.api import RouteSpec, build
        from repro.core.calibrate import calibrate_multi_tier
        from repro.retrieval.kg import KnowledgeGraph
        from repro.retrieval.scorer import batch_triple_features
        from repro.serving.router_service import BATCH_BUCKETS

        c = self.config
        data_seed = int(c["data_seed"])
        self.graph, self.ent, self.rel = datagen.make_kg(
            int(c["n_entities"]), int(c["n_relations"]),
            float(c["avg_degree"]), int(c["structure_dim"]), int(c["d_emb"]),
            seed=data_seed)
        g = self.graph
        self.kg = KnowledgeGraph.build(g.heads, g.rels, g.tails,
                                       g.n_entities, g.n_relations)
        self.pool = datagen.make_queries(g, self.ent, self.rel,
                                         int(self.mix["pool"]),
                                         c["dataset"], seed=data_seed + 1)
        counts = [len(self._candidates(q)) for q in self.pool]
        self.params = init_weights(self.seed, *self.widths)
        self.session = build(RouteSpec(
            metric=c["metric"], thresholds=(0.0,) * (len(c["tier_shares"]) - 1),
            cumulative_p=c["cumulative_p"], top_k=self.k,
            tier_names=tuple(c["tier_names"]), backend=c["backend"],
            crossover_batch=c["crossover_batch"],
            micro_batch=c["micro_batch"]))

        def route(questions):
            feats, qembs, _, n_cand = batch_triple_features(
                self.kg, self.ent, self.rel, questions,
                max_cands=int(c["max_cands"]), seed=int(c["candidate_seed"]))
            return self.session.route_retrieved(feats, qembs, self.params,
                                                n_cand=n_cand)

        # every (batch bucket, candidate count) the pool can produce:
        # copies of one question of each count fill the bucket's smallest
        # batch, and the program pads them to the bucket
        sizes = traffic.batch_sizes(self.mix, BATCH_BUCKETS)
        first = {}
        for qi, n in enumerate(counts):
            first.setdefault(n, qi)
        for lo, _ in sizes:
            for qi in first.values():
                route([self.pool[qi]] * lo)
        # thresholds from a seeded sample of the pool, in calls that fill
        # the largest warmed bucket
        gen = traffic.rng(self.seed, traffic.STREAM_CALIBRATION)
        self.cal_ids = gen.choice(len(self.pool), int(c["calibration_questions"]),
                                  replace=False)
        probs, n_valid = [], []
        step = min(b for b in BATCH_BUCKETS if b >= sizes[-1][1])
        for s in range(0, len(self.cal_ids), step):
            res = route([self.pool[q] for q in self.cal_ids[s:s + step]])
            probs.append(res.probs)
            n_valid.append(res.n_valid)
        probs, n_valid = np.concatenate(probs), np.concatenate(n_valid)
        mask = np.arange(probs.shape[1])[None, :] < n_valid[:, None]
        fitted = calibrate_multi_tier(
            jnp.asarray(probs), c["tier_shares"], metric=c["metric"],
            cumulative_p=c["cumulative_p"], mask=jnp.asarray(mask))
        self.session.dispatcher.apply_config(fitted)
        self.order = traffic.rng(self.seed, traffic.STREAM_ORDER).integers(
            0, len(self.pool), 1 << 16)
        self._features = batch_triple_features
        self.outputs = []

    def _candidates(self, q) -> np.ndarray:
        return reference.candidates(self.graph, q.topic, q.hops, q.gold_edges,
                                    int(self.config["max_cands"]),
                                    int(self.config["candidate_seed"]))

    def question_ids(self, i: int, j: int) -> np.ndarray:
        return self.order[np.arange(i, j) % len(self.order)]

    def serve(self, i: int, j: int) -> None:
        c = self.config
        qids = self.question_ids(i, j)
        with self.spans.span("feature_build", j - i):
            feats, qembs, _, n_cand = self._features(
                self.kg, self.ent, self.rel, [self.pool[q] for q in qids],
                max_cands=int(c["max_cands"]), seed=int(c["candidate_seed"]))
        with self.spans.span("session_call", j - i):
            res = self.session.route_retrieved(feats, qembs, self.params,
                                               n_cand=n_cand)
        self.outputs.append((i, n_cand, res.indices, res.probs, res.n_valid,
                             res.tiers, res.result.difficulty,
                             res.result.metrics))

    def release(self) -> None:
        self.weights = {k: np.asarray(v) for k, v in self.params.items()}
        self.session = self.params = None

    def served_ids(self) -> np.ndarray:
        if not self.outputs:
            return np.zeros(0, np.int64)
        return np.concatenate([np.arange(o[0], o[0] + len(o[5]))
                               for o in self.outputs])

    def n_cand_of_calls(self) -> list:
        """Real candidate counts of the questions of each call."""
        return [o[1] for o in self.outputs]

    # -- correctness --------------------------------------------------------

    def _sample(self, window) -> tuple[np.ndarray, dict]:
        """A seeded sample of the requests served in the window, with the
        one that had most candidates, and their outputs."""
        ids = self.served_ids()
        cols = [np.concatenate([o[n] for o in self.outputs])
                for n in range(1, 8)] if self.outputs else None
        keep = np.flatnonzero(ids < window.n_due)
        if not len(keep):
            return ids[:0], {}
        gen = traffic.rng(self.seed, traffic.STREAM_SAMPLE)
        size = min(int(self.config["check_requests"]), len(keep))
        pick = set(gen.choice(keep, size, replace=False).tolist())
        pick.add(int(keep[np.argmax(cols[0][keep])]))
        pick = np.asarray(sorted(pick))
        names = ("n_cand", "indices", "probs", "n_valid", "tiers",
                 "difficulty", "metrics")
        return ids[pick], {n: col[pick] for n, col in zip(names, cols)}

    def _ref_logits(self, qids, matmul=None) -> dict:
        """Reference logits of each pool question in ``qids``: float64, or
        float32 features through ``matmul``."""
        w = self.weights
        out = {}
        for q in sorted(set(int(x) for x in qids)):
            question = self.pool[q]
            feats = reference.features(self.graph, self.ent, self.rel,
                                       question.topic, question.query_emb,
                                       self._candidates(question))
            if matmul is None:
                out[q] = reference.mlp_logits(feats, question.query_emb, w)
            else:
                out[q] = reference.mlp_logits(feats.astype(np.float32),
                                              question.query_emb, w, matmul)
        return out

    def _retrieve(self, logits: dict, qids) -> tuple:
        """Top-K indices, their sigmoid scores (float64, zero past the
        valid count) and valid counts, from each question's logits."""
        idx = np.zeros((len(qids), self.k), np.int64)
        probs = np.zeros((len(qids), self.k))
        nv = np.zeros(len(qids), np.int64)
        for r, q in enumerate(qids):
            lg = logits[int(q)]
            top = reference.top_k(lg, min(self.k, len(lg)))
            idx[r, :len(top)] = top
            probs[r, :len(top)] = reference.sigmoid(lg[top])
            nv[r] = len(top)
        return idx, probs, nv

    def _difficulty(self, probs, nv) -> np.ndarray:
        m, _ = reference.skew_metrics(probs, nv, self.config["cumulative_p"])
        return reference.difficulty(m, self.config["metric"])

    def _numbers(self, window, qids, got: dict, logits: dict) -> dict:
        """The metric stage against the reference on the scores it was
        given; retrieval and tiers against the reference's own retrieval
        of each question, with thresholds it calibrated itself."""
        c = self.config
        p = c["cumulative_p"]
        stage_m, stage_cdf = reference.skew_metrics(got["probs"],
                                                    got["n_valid"], p)
        numbers = compare.metric_numbers(got["metrics"], got["difficulty"],
                                         stage_m, stage_cdf, c["metric"], p)
        _, ref_probs, ref_nv = self._retrieve(logits, qids)
        _, cal_probs, cal_nv = self._retrieve(logits, self.cal_ids)
        thr = reference.calibrate(self._difficulty(cal_probs, cal_nv),
                                  c["tier_shares"])
        numbers.update(compare.decision_numbers(
            got["difficulty"], got["tiers"],
            self._difficulty(ref_probs, ref_nv), thr))
        numbers.update(compare.retrieval_numbers(
            got["indices"], got["probs"], got["n_valid"],
            [logits[int(q)] for q in qids]))
        served = self.served_ids()
        numbers["missing"] = window.n_due - len(np.unique(
            served[served < window.n_due]))
        return numbers

    def check(self, window) -> dict:
        ids, got = self._sample(window)
        if not len(ids):
            return {"metric_err": 0.0, "cdf_gap": 0.0, "decision_err": 0.0,
                    "score_err": 0.0, "missing": window.n_due}
        qids = self.order[ids % len(self.order)]
        logits = self._ref_logits(np.concatenate([qids, self.cal_ids]))
        return self._numbers(window, qids, got, logits)

    def control(self, window) -> dict:
        """The reference one precision lower, on the device, in the
        program's place for the requests the check samples: its matmuls at
        ``high`` precision (float32 features, bfloat16x3 products), its
        sigmoid scores and skew metrics in bfloat16, its own calibration."""
        import jax
        import jax.numpy as jnp
        c = self.config
        ids, _ = self._sample(window)
        qids = self.order[ids % len(self.order)]
        all_q = np.concatenate([qids, self.cal_ids])
        ctl = self._ref_logits(all_q, matmul=dot_high)

        def lower(q_ids):
            idx, _, nv = self._retrieve(ctl, q_ids)
            top = np.stack([np.asarray(ctl[int(q)])[i] for q, i in
                            zip(q_ids, idx)])
            probs = jax.nn.sigmoid(jnp.asarray(top, jnp.bfloat16))
            probs = jnp.where(jnp.arange(self.k)[None, :] < nv[:, None],
                              probs, 0)
            m, _ = reference.skew_metrics(probs, nv, c["cumulative_p"],
                                          xp=jnp, dtype=jnp.bfloat16)
            as64 = (lambda a: np.asarray(jnp.asarray(a, jnp.float32),
                                         np.float64))
            return idx, as64(probs), nv, as64(m)

        idx, probs, nv, m = lower(qids)
        cal_m = lower(self.cal_ids)[3]
        thr = reference.calibrate(reference.difficulty(cal_m, c["metric"]),
                                  c["tier_shares"])
        diff = reference.difficulty(m, c["metric"])
        got = {"metrics": m, "difficulty": diff,
               "tiers": reference.tiers(diff, thr), "indices": idx,
               "probs": probs, "n_valid": nv}
        return self._numbers(window, qids, got,
                             self._ref_logits(all_q))
