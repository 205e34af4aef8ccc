"""SkewRoute dispatcher: retrieval scores in, tier assignment out.

This is the paper's Algorithm 1 as a serving component, running on the
FUSED fast path. Per batch:

  1. the retrieval stage hands over the top-K triple scores (descending,
     optionally ragged via per-row ``n_valid``);
  2. the attached :class:`repro.api.backends.DifficultyBackend` (fused
     Pallas pass by default — ``auto``; interpret mode off-TPU) computes
     all four difficulty metrics in one call — the configured metric is
     a column select, never a recompile;
  3. the threshold router picks tiers; telemetry (tier counts, expected
     $ cost, mean difficulty) streams to the stats sink;
  4. difficulty samples feed the attached streaming calibrator
     (``core.streaming_calibrate``), which hot-swaps the thresholds when
     live traffic drifts off the calibrated tier shares;
  5. requests join their tier's micro-batch queue
     (``serving/scheduler.MicroBatchQueue`` via ``serving/pipeline``).

Batch shapes are bucketed (pad to the next bucket, slice the pad off) so
arbitrary request-batch sizes reuse a handful of compiled kernels.

Thresholds stay *hot-swappable*: both the offline calibrator
(core/calibrate.py) and the online one can re-fit them from unlabeled
samples without touching the serving path — the training-free property
operationalized.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.calibrate import calibrate_multi_tier
from repro.core.cost import CostModel
from repro.core.router import RouterConfig, unpack_decision, unpack_kg
from repro.core.streaming_calibrate import StreamingCalibrator
from repro.kernels.triple_score.kernel import DEFAULT_TILE as SCORER_TILE
from repro.obs import NULL_OBS, str_keyed, int_keyed
from repro.serving import _deprecation
from repro.serving.scheduler import bucket_size

BATCH_BUCKETS = (8, 64, 256, 1024, 4096)


@dataclasses.dataclass
class DispatchRecord:
    request_id: int
    tier: int
    difficulty: float
    metric: str


@dataclasses.dataclass
class BatchDispatchResult:
    """Per-batch fast-path output plus what the control plane did with it.

    ``records`` is built lazily on first access: array-only consumers
    (telemetry, the recsys example, bulk routing) never pay the
    per-request Python object loop.
    """

    tiers: np.ndarray         # [B] int32
    difficulty: np.ndarray    # [B] float32
    metrics: np.ndarray       # [B, 4] float32 (area, cum_k, entropy, gini)
    first_id: int = 0
    metric: str = ""
    recalibrated: bool = False
    # Routing-policy extras (None under the default threshold policy):
    # per-request $ the decision actually costs (cascades bill every
    # stage attempted) and per-request retrieval depth.
    request_cost: Optional[np.ndarray] = None
    depths: Optional[np.ndarray] = None

    @functools.cached_property
    def records(self) -> list[DispatchRecord]:
        return [DispatchRecord(request_id=self.first_id + i,
                               tier=int(self.tiers[i]),
                               difficulty=float(self.difficulty[i]),
                               metric=self.metric)
                for i in range(len(self.tiers))]


@dataclasses.dataclass
class RetrievedDispatchResult:
    """End-to-end dispatch output: the routing decision plus the top-K
    retrieval the fused program produced on the way (candidate indices
    into the per-query feature rows, sigmoid scores, valid prefix)."""

    result: BatchDispatchResult
    indices: np.ndarray       # [B, K] int32
    probs: np.ndarray         # [B, K] float32, descending
    n_valid: np.ndarray       # [B] int32

    @property
    def tiers(self) -> np.ndarray:
        return self.result.tiers


@dataclasses.dataclass
class QuestionRecord:
    """What a tier runner receives for one question: its request id, the
    tier it runs on, its top-K triple ids (best first) and the caller's
    payload, if any."""

    request_id: int
    tier: int
    triple_ids: np.ndarray    # [n_valid] int32
    payload: object = None


@dataclasses.dataclass
class QuestionDispatchResult:
    """A question batch's decisions (:class:`BatchDispatchResult`) and its
    top-K retrieval: triple ids from the store, sigmoid scores, valid
    prefix (ids are -1 and scores 0 past it)."""

    result: BatchDispatchResult
    triple_ids: np.ndarray    # [B, K] int32
    probs: np.ndarray         # [B, K] float32, descending
    n_valid: np.ndarray       # [B] int32

    @property
    def tiers(self) -> np.ndarray:
        return self.result.tiers

    def handoffs(self, tiers: np.ndarray,
                 payloads: Optional[Sequence] = None) -> list:
        """One :class:`QuestionRecord` per question, for ``tiers`` (the
        tiers the questions execute on)."""
        first = self.result.first_id
        payloads = payloads if payloads is not None else [None] * len(tiers)
        return [QuestionRecord(first + i, t, self.triple_ids[i, :nv], p)
                for i, (t, nv, p) in enumerate(zip(
                    tiers.tolist(), self.n_valid.tolist(), payloads))]


@dataclasses.dataclass
class DispatcherStats:
    n_requests: int = 0
    n_batches: int = 0
    n_recalibrations: int = 0
    tier_counts: dict = dataclasses.field(default_factory=dict)
    total_cost: float = 0.0
    mean_difficulty: float = 0.0  # running mean over all dispatched requests

    @property
    def large_call_ratio(self) -> float:
        if not self.n_requests:
            return 0.0
        top = max(self.tier_counts) if self.tier_counts else 0
        return self.tier_counts.get(top, 0) / self.n_requests

    # -- serializable state (the single source of the counter list) ----------

    def state_dict(self) -> dict:
        return {
            "n_requests": self.n_requests,
            "n_batches": self.n_batches,
            "n_recalibrations": self.n_recalibrations,
            "tier_counts": str_keyed(self.tier_counts),
            "total_cost": self.total_cost,
            "mean_difficulty": self.mean_difficulty,
        }

    def load_state_dict(self, state: dict) -> None:
        self.n_requests = int(state["n_requests"])
        self.n_batches = int(state["n_batches"])
        self.n_recalibrations = int(state["n_recalibrations"])
        self.tier_counts = int_keyed(state["tier_counts"])
        self.total_cost = float(state["total_cost"])
        self.mean_difficulty = float(state["mean_difficulty"])


def _cut_to_depths(depths, n_valid, probs, ids):
    """Depth routing: the candidate set each request SHIPS is its routed
    depth. The valid prefix shrinks to it, and the scores past it read 0
    and the ids -1, so downstream consumers can't read truncated rows."""
    n_valid = np.minimum(n_valid, depths).astype(np.int32)
    past = np.arange(probs.shape[1])[None, :] >= n_valid[:, None]
    return (n_valid, np.where(past, 0.0, probs).astype(probs.dtype),
            np.where(past, -1, ids).astype(np.int32))


def _pad_rows(x: np.ndarray, bpad: int, fill=0) -> np.ndarray:
    """``x`` with rows of ``fill`` appended up to the batch bucket's
    ``bpad`` rows; ``x`` itself where it already has them."""
    if len(x) == bpad:
        return x
    return np.concatenate(
        [x, np.full((bpad - len(x),) + x.shape[1:], fill, x.dtype)])


class SkewRouteDispatcher:
    def __init__(self, router: RouterConfig, tier_names: Sequence[str],
                 cost_model: Optional[CostModel] = None,
                 calibrator: Optional[StreamingCalibrator] = None,
                 backend=None, policy=None, obs=None):
        _deprecation.warn_once(
            "SkewRouteDispatcher",
            "hand-wiring SkewRouteDispatcher is deprecated; declare the "
            "policy as a repro.api.RouteSpec and call repro.api.build(spec) "
            "(see README 'Routing fast path')")
        if len(tier_names) != router.n_tiers:
            raise ValueError(f"{router.n_tiers} tiers but "
                             f"{len(tier_names)} tier names")
        if backend is None:
            # lazy import: repro.api composes this class, not vice versa
            from repro.api.backends import make_backend
            backend = make_backend("auto")
        self.backend = backend
        self.router = router
        self.tier_names = list(tier_names)
        self.cost_model = cost_model or CostModel()
        self.calibrator = calibrator
        if policy is None:
            # lazy import for the same layering reason as the backend
            from repro.policies import build_policy
            policy = build_policy(None, n_tiers=router.n_tiers,
                                  tier_models=tier_names,
                                  cost_model=self.cost_model)
        self.policy = policy
        self.stats = DispatcherStats(tier_counts={i: 0 for i in
                                                  range(router.n_tiers)})
        self._lock = threading.Lock()
        self._next_id = 0
        # Observability mirrors: instruments looked up ONCE here; every
        # record below is a plain attribute bump (no-ops under NULL_OBS).
        # DispatcherStats stays the serialization source; the registry is
        # the live read surface (old accessors preserved as views).
        self.obs = obs or NULL_OBS
        m = self.obs.metrics
        self._m_requests = m.counter("routing_requests_total")
        self._m_batches = m.counter("routing_batches_total")
        self._m_recal = m.counter("routing_recalibrations_total")
        self._m_cost = m.counter("routing_cost_dollars_total")
        self._m_mean_diff = m.gauge("routing_mean_difficulty")
        self._m_dispatch_s = m.histogram("routing_dispatch_seconds")
        self._m_tiers = [m.counter("routing_tier_decisions_total",
                                   tier=str(t))
                         for t in range(router.n_tiers)]
        self._m_h2d = m.counter("dispatch_transfers_total", direction="h2d")
        self._m_d2h = m.counter("dispatch_transfers_total", direction="d2h")
        self._m_kg_cands = m.counter("kg_candidates_total")
        self._m_kg_tiles_scored = m.counter("kg_scorer_tiles_total",
                                            state="scored")
        self._m_kg_tiles_skipped = m.counter("kg_scorer_tiles_total",
                                             state="skipped")

    def _obs_resync(self) -> None:
        """Point the registry's dispatcher mirrors at the (restored)
        stats — called by the session after a state restore so the live
        metrics agree with the restored counters."""
        if not self.obs.enabled:
            return
        s = self.stats
        self._m_requests.value = s.n_requests
        self._m_batches.value = s.n_batches
        self._m_recal.value = s.n_recalibrations
        self._m_cost.value = s.total_cost
        self._m_mean_diff.value = s.mean_difficulty
        for t, mt in enumerate(self._m_tiers):
            mt.value = s.tier_counts.get(t, 0)

    # -- calibration ----------------------------------------------------------

    def attach_calibrator(self, target_shares: Sequence[float],
                          **knobs) -> StreamingCalibrator:
        """Wire a drift-aware streaming calibrator into the dispatch flow."""
        self.calibrator = StreamingCalibrator(self.router, target_shares,
                                              **knobs)
        return self.calibrator

    def apply_config(self, new_router: RouterConfig,
                     quantile_source=None) -> None:
        """THE threshold hot-swap path — offline recalibration, the
        streaming drift calibrator, the admission controller, and the
        replica-sync merge all land here: swap the frozen config, keep
        the calibrator's view coherent, count it — and re-fit the
        routing policy's own cutoffs from the same sample set that
        produced the thresholds (``quantile_source``; defaults to the
        attached calibrator's window, replica sync passes its merged
        fleet quantile), so threshold and policy calibration can never
        diverge."""
        with self._lock:
            self.router = new_router
            self.stats.n_recalibrations += 1
            self._m_recal.inc()
            if self.calibrator is not None:
                self.calibrator.config = new_router
            self._refit_policy_locked(quantile_source)
        if self.obs.enabled:
            self.obs.tracer.event(
                "hot_swap", thresholds=list(new_router.thresholds),
                metric=new_router.metric)

    def _refit_policy_locked(self, quantile_source=None) -> None:
        """Policy-cutoff refit half of a hot-swap; caller holds the lock."""
        if not self.policy.needs_refit:
            return
        if quantile_source is None:
            cal = self.calibrator
            if cal is None or len(cal.window) < cal.min_samples:
                return  # nothing trustworthy to fit from yet
            quantile_source = cal.quantile_source()
        self.policy.refit(quantile_source)

    def recalibrate(self, calibration_scores: np.ndarray,
                    tier_shares: Sequence[float]) -> RouterConfig:
        """Hot-swap thresholds to hit new traffic shares (training-free)."""
        new_router = calibrate_multi_tier(
            jnp.asarray(calibration_scores), tier_shares,
            metric=self.router.metric, cumulative_p=self.router.cumulative_p)
        self.apply_config(new_router)
        return new_router

    # -- dispatch -------------------------------------------------------------

    def _put(self, *host_arrays) -> tuple:
        """A dispatch's one host-to-device transfer: every input of the
        device program in a single ``jax.device_put``."""
        self._m_h2d.inc()
        return jax.device_put(host_arrays)

    def _pull(self, b: int, *device_arrays) -> list:
        """A dispatch's one device-to-host read: ``jax.device_get`` starts
        every copy before it blocks; the bucket padding is sliced off on
        the host."""
        self._m_d2h.inc()
        return [a[:b] for a in jax.device_get(device_arrays)]

    def _backend_entry(self, name: str):
        """The backend's ``name`` method; a TypeError where it has none."""
        entry = getattr(self.backend, name, None)
        if entry is None:
            raise TypeError(
                f"difficulty backend {self.backend.name!r} has no {name}; "
                f"this dispatch needs one of the built-in backends (oracle | "
                f"pallas | fused | auto) or a custom backend implementing it")
        return entry

    def _dispatch(self, span: str, b: int, inputs, launch, unpack,
                  self_scores: Optional[np.ndarray] = None):
        """What every dispatch entry shares, in the entry's ``span``:
        ``inputs(bpad)`` pads the host inputs to the batch bucket; one
        transfer; ``launch`` starts the backend's program on them; one
        read; ``unpack`` gives tiers, difficulty, metrics and any
        retrieval arrays (valid prefix, scores, ids); the policy decides
        and the batch is recorded. Returns the :class:`BatchDispatchResult`
        and the retrieval arrays, cut to the decision's depths if any."""
        tracer = self.obs.tracer
        obs_on = self.obs.enabled
        with tracer.span(span, batch=b):
            t0 = self.obs.clock.now() if obs_on else 0.0
            with tracer.span("launch"):
                bpad = bucket_size(b, BATCH_BUCKETS)
                out = launch(*self._put(*inputs(bpad)))
            with tracer.span("pull"):
                tiers, diff, metrics, *retrieved = unpack(*self._pull(b, *out))
            if obs_on:  # the pull forced the device sync above
                self._m_dispatch_s.observe(self.obs.clock.now() - t0)
            with tracer.span("decide"):
                decision = self.policy.decide(tiers, diff, metrics,
                                              self_scores=self_scores)
                first_id, metric_name, recalibrated = self._record_batch(
                    decision.tiers, diff, decision, backend_tiers=tiers)
        if retrieved and decision.depths is not None:
            retrieved = _cut_to_depths(decision.depths, *retrieved)
        return BatchDispatchResult(
            tiers=decision.tiers, difficulty=diff, metrics=metrics,
            first_id=first_id, metric=metric_name, recalibrated=recalibrated,
            request_cost=decision.request_cost,
            depths=decision.depths), retrieved

    def dispatch(self, scores_desc: np.ndarray,
                 n_valid: Optional[int] = None) -> DispatchRecord:
        """Route one request — same fused kernel, batch of one (bucketed
        to the smallest batch bucket, so it shares the compiled kernel
        with every other small batch)."""
        nv = None if n_valid is None else np.asarray([n_valid])
        return self.dispatch_batch(np.asarray(scores_desc)[None], n_valid=nv,
                                   return_details=True).records[0]

    def dispatch_batch(self, scores_desc: np.ndarray,
                       n_valid: Optional[np.ndarray] = None,
                       return_details: bool = False,
                       self_scores: Optional[np.ndarray] = None):
        """[B, K] (+ optional [B] n_valid) -> [B] tier ids.

        The vectorized fast path: one fused kernel call per bucketed batch
        shape. With ``return_details=True`` returns a
        :class:`BatchDispatchResult` carrying per-request records and the
        full metric matrix (the pipeline and telemetry consumers).
        ``self_scores``: optional [B] engine self-uncertainty (higher =
        less confident) some policies (cascade) fold into the decision.
        """
        route, router = self._backend_entry("route_batch"), self.router
        scores = np.asarray(scores_desc)
        b, k = scores.shape
        # always pass a concrete n_valid so every bucket shape compiles
        # the kernel exactly once (None vs array would be two traces);
        # padding rows hold one valid score: degenerate but well-defined
        nv = np.full(b, k, np.int32)
        if n_valid is not None:
            nv[:] = np.asarray(n_valid, np.int32)
        result, _ = self._dispatch(
            "dispatch", b,
            lambda bpad: (_pad_rows(scores, bpad), _pad_rows(nv, bpad, 1)),
            lambda s, v: (route(s, router, n_valid=v).decision,),
            lambda buf: unpack_decision(buf, router.metric),
            self_scores=self_scores)
        return result if return_details else result.tiers

    def dispatch_retrieved(self, feats: np.ndarray, query_emb: np.ndarray,
                           scorer_params, n_cand: Optional[np.ndarray] = None
                           ) -> "RetrievedDispatchResult":
        """End-to-end dispatch from candidate features: ONE device program
        (scoring -> top-k -> skew -> decision; see
        `repro.core.router.route_retrieved`). Telemetry and streaming
        calibration update exactly as for :meth:`dispatch_batch`.

        ``feats``: [B, N, Dt]; ``query_emb``: [B, Dq]; ``n_cand``:
        optional [B] real candidate counts (ragged retrieval). The
        bucket's padding rows hold no candidates.
        """
        route, router = self._backend_entry("route_retrieved"), self.router
        feats = np.asarray(feats)
        qemb = np.asarray(query_emb)
        b, n, _ = feats.shape
        nc = np.full(b, n, np.int32)
        if n_cand is not None:
            nc[:] = np.asarray(n_cand, np.int32)

        def launch(f, q, c):
            res = route(f, q, scorer_params, router, n_cand=c)
            return (res.tiers, res.difficulty, res.metrics, res.n_valid,
                    res.probs, res.indices)
        result, (nv_out, probs, indices) = self._dispatch(
            "dispatch_retrieved", b,
            lambda bpad: (_pad_rows(feats, bpad), _pad_rows(qemb, bpad),
                          _pad_rows(nc, bpad)),
            launch, lambda *pulled: pulled)
        return RetrievedDispatchResult(result=result, indices=indices,
                                       probs=probs, n_valid=nv_out)

    def dispatch_kg(self, store, scorer_params, topics, query_emb,
                    cand_ids, n_cand) -> QuestionDispatchResult:
        """Questions over a knowledge graph on the device: ONE device
        program (`repro.core.router.route_kg`) gathers each candidate's
        features from ``store`` (a `repro.retrieval.store.DeviceKG`),
        scores them, keeps the top K and decides; one transfer of the
        inputs, one read of the packed result.

        ``topics``: [B] topic entities; ``query_emb``: [B, d];
        ``cand_ids``: [B, N] candidate triple ids, N <= ``store.max_cands``,
        of which the first ``n_cand[i]`` (1 .. N) of row i are real. Each
        call is padded to ``store.max_cands`` slots and a batch bucket;
        the bucket's padding rows hold no candidates, so the scorer kernel
        skips all their tiles. ``kg_scorer_tiles_total{state}`` counts the
        scorer grid's tiles of each call, scored and skipped.
        """
        route, router = self._backend_entry("route_kg"), self.router
        topics = np.asarray(topics)
        cand = np.asarray(cand_ids)
        nc = np.asarray(n_cand, np.int32)
        qemb = np.asarray(query_emb, np.float32)
        b, n = cand.shape
        if not (topics.shape == nc.shape == (b,) and
                qemb.shape == (b, store.d_emb)):
            raise ValueError(
                f"{b} questions of candidate ids, but topics {topics.shape}, "
                f"n_cand {nc.shape} and query_emb {qemb.shape} (width "
                f"{store.d_emb})")
        if n > store.max_cands or nc.min(initial=1) < 1 or \
                nc.max(initial=1) > n:
            raise ValueError(
                f"candidate counts must lie in 1 .. {n} and the store holds "
                f"{store.max_cands} slots; got {n} columns, counts "
                f"{nc.min(initial=1)} .. {nc.max(initial=1)}")
        ids = np.zeros((b, store.max_cands), np.int32)
        ids[:, :n] = np.where(np.arange(n)[None, :] < nc[:, None], cand, 0)
        if ids.min(initial=0) < 0 or ids.max(initial=0) >= store.n_triples \
                or topics.min(initial=0) < 0 or \
                topics.max(initial=0) >= store.n_entities:
            raise ValueError(
                f"candidate triple ids must lie in 0 .. "
                f"{store.n_triples - 1} and topics in 0 .. "
                f"{store.n_entities - 1}")
        result, (nv_out, probs, triple_ids) = self._dispatch(
            "dispatch_kg", b,
            lambda bpad: tuple(_pad_rows(x, bpad) for x in (
                topics.astype(np.int32), qemb, ids, nc)),
            lambda *inputs: (route(store, scorer_params, router, *inputs),),
            lambda buf: unpack_kg(buf, router.metric))
        bpad = bucket_size(b, BATCH_BUCKETS)
        self._m_kg_cands.inc(int(nc.sum()))
        scored = int((-(-nc // SCORER_TILE)).sum())
        self._m_kg_tiles_scored.inc(scored)
        self._m_kg_tiles_skipped.inc(
            bpad * -(-store.max_cands // SCORER_TILE) - scored)
        return QuestionDispatchResult(result=result, triple_ids=triple_ids,
                                      probs=probs, n_valid=nv_out)

    def _record_batch(self, tiers: np.ndarray, diff: np.ndarray,
                      decision=None, backend_tiers=None
                      ) -> tuple[int, str, bool]:
        """The control-plane half shared by every dispatch entry: request
        ids, tier/cost/difficulty counters, drift-aware recalibration.
        ``backend_tiers`` is the difficulty backend's threshold decision
        (pre-policy) — the trace's ``dispatch`` event carries it so a
        request's timeline shows both halves of the decision."""
        b = len(tiers)
        recalibrated = False
        with self._lock:
            metric_name = self.router.metric
            first_id = self._next_id
            self._next_id += b
            counts = np.bincount(tiers, minlength=self.router.n_tiers)
            total = self.stats.n_requests
            self.stats.n_requests += b
            self.stats.n_batches += 1
            self.stats.mean_difficulty = (
                (self.stats.mean_difficulty * total + float(diff.sum()))
                / max(self.stats.n_requests, 1))
            cost_before = self.stats.total_cost
            if decision is not None and decision.request_cost is not None:
                # The policy priced each request itself (per-stage cascade
                # bills, per-depth prompt lengths) — the ledger takes the
                # decision's word over the flat per-tier price.
                self.stats.total_cost += float(decision.request_cost.sum())
                for t, c in enumerate(counts):
                    if c:
                        self.stats.tier_counts[t] += int(c)
            else:
                for t, c in enumerate(counts):
                    if not c:
                        continue
                    self.stats.tier_counts[t] += int(c)
                    name = self.tier_names[t]
                    if name in self.cost_model.cost_per_mtok:
                        self.stats.total_cost += (
                            self.cost_model.request_cost(name) * int(c))
            # registry mirrors (no-ops under NULL_OBS)
            self._m_requests.inc(b)
            self._m_batches.inc()
            self._m_cost.inc(self.stats.total_cost - cost_before)
            self._m_mean_diff.set(self.stats.mean_difficulty)
            for t, c in enumerate(counts):
                if c:
                    self._m_tiers[t].inc(int(c))
            if self.calibrator is not None:
                new_config = self.calibrator.observe(diff)
                if new_config is not None:
                    self.router = new_config
                    self.stats.n_recalibrations += 1
                    self._m_recal.inc()
                    recalibrated = True
                    # An inline drift swap re-fits the policy from the
                    # window that produced the new thresholds (same rule
                    # as apply_config; we already hold the lock).
                    self._refit_policy_locked()
        if self.obs.enabled:
            # Batch-granularity trace events: one "dispatch" (the
            # backend's threshold tiers) + one "policy" (the final
            # decision) carrying first_id + per-row tiers — the export
            # walker re-expands them into per-request timelines.
            # ndarrays go in raw: the tracer's _jsonable hits the
            # one-shot ndarray->tolist branch instead of walking a
            # python list per element (measured on the 5% overhead gate)
            tr = self.obs.tracer
            bt = tiers if backend_tiers is None else backend_tiers
            tr.event("dispatch", first_id=first_id,
                     tiers=np.asarray(bt), metric=metric_name)
            attrs = {"first_id": first_id, "kind": self.policy.kind,
                     "tiers": np.asarray(tiers)}
            if backend_tiers is not None and \
                    not np.array_equal(bt, tiers):
                attrs["tiers_in"] = np.asarray(bt)  # policy overrode rows
            if decision is not None and decision.info:
                attrs.update(decision.info)
            tr.event("policy", **attrs)
            if recalibrated:
                tr.event("recalibrate", first_id=first_id,
                         thresholds=list(self.router.thresholds))
        return first_id, metric_name, recalibrated
