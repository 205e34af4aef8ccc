"""Pre-scored deployments: a retriever hands the router each question's
top-K triple scores, and `SkewRouteSession.submit` routes them.

The tier runners only record the hand-off, the ids of the requests they
receive: the tier LLMs live outside the router's process. Thresholds are calibrated once in set-up, on a seeded
sample, to the configured tier shares, and stay static in the window.
"""

from __future__ import annotations

import copy

import numpy as np

from perfbench import compare, datagen, reference, traffic


def small(config: dict, mix: dict) -> tuple[dict, dict]:
    """Copies of a configuration and mix cut for the CPU tests: a pool of
    2,048 rows and, in an open loop, 1,000 arrivals a second; K and the
    batches as run."""
    config, mix = copy.deepcopy((config, mix))
    mix["pool"] = 2048
    if mix["loop"] == "open":
        mix["rate"] = 1000
    return config, mix


class Deployment:
    #: where each entry of ``outputs`` holds the served tiers
    tiers_column = 1
    #: whether requests reach tier runners through the program's pipeline:
    #: ``_handoff(tier)`` builds each runner, ``session.pipeline`` queues
    hands_off = True

    def __init__(self, config: dict, mix: dict, seed: int, spans):
        self.config, self.mix, self.seed, self.spans = config, mix, seed, spans
        self.k = int(config["top_k"])
        self.max_batch = traffic.batch_limit(mix)
        #: per tier, the session's request ids its runner received
        self.handed: list[list[int]] = [[] for _ in config["tier_shares"]]
        self.outputs: list = []

    def _rows(self, stream: int, n: int):
        r = self.mix["rows"]
        return datagen.power_law_rows(
            traffic.rng(self.seed, stream), n, self.k, r["alpha_lo"],
            r["alpha_hi"], r.get("ragged_share", 0.0), r.get("ragged_lo", 1),
            r.get("ragged_hi", 1))

    def _handoff(self, tier: int):
        ids = self.handed[tier]

        def run(batch):
            ids.extend([r.request_id for r in batch])
        return run

    def setup(self) -> None:
        import jax.numpy as jnp
        from repro.api import RouteSpec, build
        from repro.core.calibrate import calibrate_multi_tier
        from repro.serving.router_service import BATCH_BUCKETS

        c = self.config
        self.pool, self.pool_nv = self._rows(traffic.STREAM_INPUTS,
                                             int(self.mix["pool"]))
        # the pool's first rows again at its end, so that any batch of
        # consecutive requests is one contiguous slice
        self.rows = np.concatenate([self.pool, self.pool[:self.max_batch]])
        self.rows_nv = np.concatenate([self.pool_nv,
                                       self.pool_nv[:self.max_batch]])
        self.cal, self.cal_nv = self._rows(traffic.STREAM_CALIBRATION,
                                           int(c["calibration_rows"]))
        mask = np.arange(self.k)[None, :] < self.cal_nv[:, None]
        fitted = calibrate_multi_tier(
            jnp.asarray(self.cal), c["tier_shares"], metric=c["metric"],
            cumulative_p=c["cumulative_p"], mask=jnp.asarray(mask))
        spec = RouteSpec(
            metric=c["metric"], thresholds=fitted.thresholds,
            cumulative_p=c["cumulative_p"], top_k=self.k,
            tier_names=tuple(c["tier_names"]), backend=c["backend"],
            crossover_batch=c["crossover_batch"],
            micro_batch=c["micro_batch"])
        self.session = build(spec, runners={
            t: self._handoff(t) for t in range(len(c["tier_shares"]))})
        # the smallest and largest batch of every bucket this mix's calls
        # land in, twice each
        for lo, hi in traffic.batch_sizes(self.mix, BATCH_BUCKETS):
            for _ in range(2):
                for b in (lo, hi):
                    self.session.submit(self.pool[:b], n_valid=self.pool_nv[:b])
        self.session.flush()
        self.outputs = []
        for ids in self.handed:
            ids.clear()

    def serve(self, i: int, j: int) -> None:
        k0 = i % len(self.pool)
        rows = self.rows[k0:k0 + (j - i)]
        with self.spans.span("session_call", j - i):
            res = self.session.submit(rows, n_valid=self.rows_nv[k0:k0 + (j - i)])
        self.outputs.append((i, res.tiers, res.difficulty, res.metrics,
                             res.first_id))

    def release(self) -> None:
        self.session.flush()
        self.session = None

    def n_valid_of(self, ids: np.ndarray) -> np.ndarray:
        return self.pool_nv[np.asarray(ids) % len(self.pool)]

    def served_ids(self) -> np.ndarray:
        if not self.outputs:
            return np.zeros(0, np.int64)
        return np.concatenate([np.arange(o[0], o[0] + len(o[1]))
                               for o in self.outputs])

    def handed_tiers(self) -> np.ndarray:
        """Per request in `served_ids` order, the tier whose runner received
        it once the pipeline was flushed: -1 where no runner did, -2 where
        more than one hand-off carried it."""
        if not self.outputs:
            return np.zeros(0, np.int64)
        sid = np.concatenate([np.arange(o[4], o[4] + len(o[1]))
                              for o in self.outputs])
        order = np.argsort(sid, kind="stable")
        ranked = sid[order]
        tier = np.full(len(sid), -1)
        hits = np.zeros(len(sid), np.int64)
        for t, got in enumerate(self.handed):
            got = np.asarray(got, np.int64)
            pos = np.minimum(np.searchsorted(ranked, got), len(sid) - 1)
            rows = order[pos[ranked[pos] == got]]
            np.add.at(hits, rows, 1)
            tier[rows] = t
        tier[hits > 1] = -2
        return tier

    # -- correctness --------------------------------------------------------

    def _got(self, window):
        ids = self.served_ids()
        keep = ids < window.n_due
        cat = [np.concatenate([o[n] for o in self.outputs]) if self.outputs
               else np.zeros((0,) if n < 3 else (0, 4)) for n in (1, 2, 3)]
        tiers, diff, metrics = (a[keep] for a in cat)
        return ids[keep], tiers, diff, metrics, self.handed_tiers()[keep]

    def _numbers(self, window, ids, tiers, diff, metrics, handed) -> dict:
        """Returned and handed-off decisions against the reference; a
        request due in the window is ``missing`` where it got no decision,
        or where no runner, or more than one, received it."""
        c = self.config
        p = c["cumulative_p"]
        ref_m, ref_cdf = reference.skew_metrics(self.pool, self.pool_nv, p)
        cal_m, _ = reference.skew_metrics(self.cal, self.cal_nv, p)
        thr = reference.calibrate(reference.difficulty(cal_m, c["metric"]),
                                  c["tier_shares"])
        rows = ids % len(self.pool)
        numbers = compare.metric_numbers(metrics, diff, ref_m[rows],
                                         ref_cdf[rows], c["metric"], p)
        ref_diff = reference.difficulty(ref_m[rows], c["metric"])
        numbers.update(compare.decision_numbers(diff, tiers, ref_diff, thr))
        ran = handed >= 0
        to_runner = compare.decision_numbers(diff[ran], handed[ran],
                                             ref_diff[ran], thr)
        numbers["decision_err"] = max(numbers["decision_err"],
                                      to_runner["decision_err"])
        numbers["missing"] = window.n_due - len(np.unique(ids)) + \
            int(np.sum(~ran))
        return numbers

    def check(self, window) -> dict:
        return self._numbers(window, *self._got(window))

    def control(self, window) -> dict:
        """The reference in bfloat16, on the device, in the program's place:
        its own calibration and decisions for the requests served."""
        import jax.numpy as jnp
        c = self.config
        p = c["cumulative_p"]
        ids = self._got(window)[0]
        m, _ = reference.skew_metrics(jnp.asarray(self.pool), self.pool_nv,
                                      p, xp=jnp, dtype=jnp.bfloat16)
        cal_m, _ = reference.skew_metrics(jnp.asarray(self.cal), self.cal_nv,
                                          p, xp=jnp, dtype=jnp.bfloat16)
        m = np.asarray(m.astype(jnp.float32), np.float64)
        cal_m = np.asarray(cal_m.astype(jnp.float32), np.float64)
        thr = reference.calibrate(reference.difficulty(cal_m, c["metric"]),
                                  c["tier_shares"])
        rows = ids % len(self.pool)
        diff = reference.difficulty(m, c["metric"])[rows]
        tiers = reference.tiers(diff, thr)
        return self._numbers(window, ids, tiers, diff, m[rows], tiers)
