"""SkewRoute router: training-free, threshold-based LLM tier selection.

Implements Algorithm 1 of the paper, generalized to N tiers (paper §4.3.1
shows 3 tiers: Qwen-7b / 14b / 72b). The router consumes the *difficulty
score* (see ``repro.core.skewness`` — larger = harder) and N-1 ascending
thresholds; queries land in the lowest tier whose threshold exceeds their
difficulty.

The router is a frozen dataclass of plain floats — it is deliberately
trivial to serialize, replicate across serving replicas, and hot-swap when
the calibrator produces new thresholds (no weights, no training state).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import skewness
from repro.kernels.device import default_interpret


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """Configuration of a training-free skew router.

    Attributes:
      metric: one of ``area | cumulative | entropy | gini``.
      thresholds: ascending difficulty thresholds; ``len(thresholds) + 1``
        tiers. Queries with difficulty <= thresholds[0] go to tier 0 (the
        smallest model), etc.
      cumulative_p: the P of the cumulative-threshold metric (paper Fig. 9).
      top_k: number of retrieved contexts whose scores feed the metric.
    """

    metric: str = "gini"
    thresholds: tuple[float, ...] = (0.0,)
    cumulative_p: float = 0.95
    top_k: int = 100

    def __post_init__(self):
        if self.metric not in skewness.METRICS:
            raise ValueError(f"unknown metric {self.metric!r}; "
                             f"choose from {sorted(skewness.METRICS)}")
        if self.top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {self.top_k}")
        if not 0.0 < self.cumulative_p <= 1.0:
            raise ValueError(f"cumulative_p must be in (0, 1], "
                             f"got {self.cumulative_p}")
        if len(self.thresholds) < 1:
            raise ValueError("need at least one threshold (two tiers)")
        ts = tuple(float(t) for t in self.thresholds)
        if any(b < a for a, b in zip(ts, ts[1:])):
            raise ValueError(f"thresholds must be ascending, got {ts}")
        object.__setattr__(self, "thresholds", ts)

    @property
    def n_tiers(self) -> int:
        return len(self.thresholds) + 1


def route(scores: jax.Array, config: RouterConfig,
          mask: Optional[jax.Array] = None) -> jax.Array:
    """Assign each query to a tier. ``scores``: [..., K] -> tiers [...]."""
    diff = skewness.difficulty(scores, metric=config.metric,
                               p=config.cumulative_p, mask=mask)
    return route_from_difficulty(diff, jnp.asarray(config.thresholds))


@dataclasses.dataclass(frozen=True)
class RouteBatchResult:
    """Everything the fused fast path produces for one batch.

    The decision program returns ONE buffer, ``decision`` (see
    :func:`pack_decision`): every output of a program costs the runtime
    an allocation at dispatch and a wait at the read, and on a TPU v5e
    one output in place of three takes about 0.4 ms off each batch. The
    ``tiers`` / ``difficulty`` / ``metrics`` views are cut from it on the
    device when first read; the dispatcher reads ``decision`` once and
    cuts them on the host (:func:`unpack_decision`).

    ``metrics`` keeps ALL four metric columns (kernel order — see
    ``repro.kernels.skew_metrics.ops.METRIC_COLUMNS``) so telemetry and
    the streaming calibrator get the full picture for free.
    """

    decision: jax.Array     # [B, 5] float32: the metrics, then the tier id
    metric: str             # the configured metric ``difficulty`` selects

    @functools.cached_property
    def metrics(self) -> jax.Array:
        """[B, 4] float32 raw metric values."""
        return self.decision[:, :N_METRICS]

    @functools.cached_property
    def tiers(self) -> jax.Array:
        """[B] int32 tier ids."""
        return self.decision[:, N_METRICS].astype(jnp.int32)

    @functools.cached_property
    def difficulty(self) -> jax.Array:
        """[B] float32, larger = harder."""
        return difficulty_from_metrics(self.metrics, self.metric)


#: Metric columns at the head of a packed decision buffer.
N_METRICS = 4


def pack_decision(tiers: jax.Array, metrics: jax.Array) -> jax.Array:
    """[B] tier ids + [B, 4] metrics -> one [B, 5] float32 buffer: the
    metric columns, then the tier id (a small integer, exact in float32).
    Difficulty is left out: it is a column of ``metrics``, sign-flipped
    for gini, which :func:`difficulty_from_metrics` rebuilds exactly."""
    return jnp.concatenate(
        [metrics, tiers[:, None].astype(metrics.dtype)], axis=-1)


def unpack_decision(decision, metric: str):
    """Host inverse of :func:`pack_decision` on a read-back buffer:
    ``(tiers [B] int32, difficulty [B], metrics [B, 4])``, bit-for-bit
    the values the decision program computed."""
    metrics = decision[:, :N_METRICS]
    return (decision[:, N_METRICS].astype(np.int32),
            difficulty_from_metrics(metrics, metric), metrics)


def difficulty_from_metrics(metrics: jax.Array, metric: str) -> jax.Array:
    """Column-select one metric from the fused [B, 4] output and orient it
    as a difficulty score (larger = harder). Gini is the only metric where
    high skew = high value, so it is negated (see skewness registry)."""
    from repro.kernels.skew_metrics.kernel import METRIC_COLUMNS
    try:
        col = METRIC_COLUMNS.index(metric)
    except ValueError:
        raise ValueError(f"unknown metric {metric!r}; "
                         f"choose from {sorted(METRIC_COLUMNS)}") from None
    sign = -1.0 if metric == "gini" else 1.0
    return sign * metrics[..., col]


def _decide(scores_desc: jax.Array, thresholds: jax.Array,
            n_valid: Optional[jax.Array], *, metric: str, p_cdf: float,
            ragged: bool, use_kernel: bool, interpret: bool):
    """metrics -> column select -> threshold compare, traced into the
    program that calls it (the decision program, the retrieve-to-decision
    program, the sharded backend's per-shard body): ``(tiers, difficulty,
    metrics)``."""
    if use_kernel:
        from repro.kernels.skew_metrics import ops as skew_ops
        metrics = skew_ops.skew_metrics(scores_desc, p_cdf=p_cdf,
                                        n_valid=n_valid if ragged else None,
                                        interpret=interpret)
    else:
        from repro.kernels.skew_metrics.ref import (mask_from_n_valid,
                                                    skew_metrics_ref)
        mask = (mask_from_n_valid(n_valid, scores_desc.shape[-1])
                if ragged else None)
        metrics = skew_metrics_ref(scores_desc, p_cdf=p_cdf, mask=mask)
    diff = difficulty_from_metrics(metrics, metric)
    tiers = route_from_difficulty(diff, thresholds)
    return tiers, diff, metrics


@functools.partial(jax.jit, static_argnames=("metric", "p_cdf", "ragged",
                                             "use_kernel", "interpret"))
def _decision_program(scores_desc: jax.Array, thresholds: jax.Array,
                      n_valid: Optional[jax.Array], *, metric: str,
                      p_cdf: float, ragged: bool, use_kernel: bool,
                      interpret: bool) -> jax.Array:
    """:func:`_decide` as ONE jitted device program with ONE output, the
    packed decision buffer (:func:`pack_decision`) — a routing decision
    is a single dispatch regardless of which metric implementation (fused
    Pallas kernel or the XLA oracle) feeds it. Thresholds ride along as a
    runtime array so calibration hot-swaps never trigger a recompile."""
    tiers, _, metrics = _decide(
        scores_desc, thresholds, n_valid, metric=metric, p_cdf=p_cdf,
        ragged=ragged, use_kernel=use_kernel, interpret=interpret)
    return pack_decision(tiers, metrics)


@functools.lru_cache(maxsize=512)
def _thresholds_array(thresholds: tuple[float, ...]) -> jax.Array:
    """Device copy of a threshold tuple, cached — B=1 dispatch latency is
    overhead-dominated, and re-uploading an unchanged 8-byte array every
    call is pure overhead (hot-swaps produce a new tuple -> new entry)."""
    return jnp.asarray(thresholds)


def route_all_metrics(scores_desc: jax.Array, config: RouterConfig,
                      n_valid: Optional[jax.Array] = None,
                      interpret: Optional[bool] = None,
                      use_kernel: bool = True) -> RouteBatchResult:
    """Batched fast path: ONE device program (fused Pallas pass by
    default; interpret-mode off-TPU) computes all four skew metrics, the
    column select, and the threshold compare — no per-metric recompiles,
    no per-request calls, no host hop between metrics and decision.

    ``scores_desc``: [B, K] descending-sorted top-K retrieval scores.
    ``n_valid``: optional [B] valid-prefix counts for ragged retrieval.
    ``use_kernel=False`` swaps in the XLA oracle metrics (same single-
    program shape — what the ``oracle`` difficulty backend runs).
    """
    if interpret is None:
        interpret = default_interpret()
    decision = _decision_program(
        scores_desc, _thresholds_array(config.thresholds), n_valid,
        metric=config.metric, p_cdf=config.cumulative_p,
        ragged=n_valid is not None, use_kernel=use_kernel,
        interpret=interpret)
    return RouteBatchResult(decision=decision, metric=config.metric)


def route_from_difficulty(difficulty: jax.Array,
                          thresholds: jax.Array) -> jax.Array:
    """Bucket difficulty scores by ascending thresholds -> int32 tier ids.

    tier = #thresholds strictly below the difficulty value, i.e.
    ``difficulty <= t[0]`` -> 0 (smallest model), ``> t[-1]`` -> N-1.
    """
    return jnp.sum(difficulty[..., None] > thresholds, axis=-1).astype(jnp.int32)


def route_binary(scores: jax.Array, config: RouterConfig,
                 mask: Optional[jax.Array] = None) -> jax.Array:
    """Paper's two-tier form: True -> large LLM (F_L), False -> small (F_S)."""
    return route(scores, config, mask) > 0


@jax.jit
def select_depths(difficulty: jax.Array, depth_cutoffs: jax.Array,
                  depth_options: jax.Array) -> jax.Array:
    """Route retrieval DEPTH per query: bucket difficulty by ascending
    cutoffs (the same compare as :func:`route_from_difficulty`) and pick
    the matching depth option — easy (high-skew) queries take a shallow
    k, flat distributions the deep one. Cutoffs and options ride along
    as runtime arrays so depth-policy refits never recompile; jitted so
    the `adaptive_depth` policy's second routed axis stays a device
    program next to the decision, not a host loop."""
    bucket = route_from_difficulty(difficulty, depth_cutoffs)
    return jnp.take(jnp.asarray(depth_options, jnp.int32), bucket)


# -- end-to-end: retrieval scoring -> top-k -> skew -> decision ---------------

_NEG_INF = -1e30  # masks padded/invalid candidates out of top-k


@dataclasses.dataclass(frozen=True)
class RetrievedRouteResult:
    """Everything the fused retrieve-to-decision program produces.

    ``indices``/``probs`` are the top-K retrieval output (candidate index
    into the per-query feature rows, sigmoid score in [0, 1], descending);
    ``n_valid`` counts the usable leading entries per row (< K when a
    query had fewer than K candidates). The routing triple
    (tiers/difficulty/metrics) matches :class:`RouteBatchResult`.
    """

    indices: jax.Array      # [B, K] int32 candidate indices, desc by score
    probs: jax.Array        # [B, K] float32 sigmoid scores
    n_valid: jax.Array      # [B] int32 usable prefix length (= min(n_cand, K))
    tiers: jax.Array        # [B] int32
    difficulty: jax.Array   # [B] float32
    metrics: jax.Array      # [B, 4] float32


def topk_sigmoid_decision(logits: jax.Array, thresholds: jax.Array,
                          n_cand: Optional[jax.Array], *, top_k: int,
                          metric: str, p_cdf: float, ragged: bool,
                          use_kernel: bool, interpret: bool):
    """The decision tail shared by every retrieve-to-decision program:
    candidate logits [B, N] -> ragged mask -> device top-k -> sigmoid ->
    skew metrics -> threshold compare. Factored out so the mesh-sharded
    backend (which gathers per-shard logits over the candidate axis
    first) runs BYTE-IDENTICAL math after its all_gather — parity with
    the single-device program is structural, not coincidental."""
    b, n = logits.shape
    if ragged:
        nc = jnp.clip(jnp.asarray(n_cand, jnp.int32), 1, n)
        col = jnp.arange(n, dtype=jnp.int32)[None, :]
        logits = jnp.where(col < nc[:, None], logits, _NEG_INF)
        nv = jnp.minimum(nc, top_k)
    else:
        nv = jnp.full((b,), min(n, top_k), jnp.int32)
    vals, idx = jax.lax.top_k(logits, top_k)      # descending by score
    probs = jax.nn.sigmoid(vals)                  # paper scores are [0, 1]
    tiers, diff, metrics = _decide(
        probs, thresholds, nv, metric=metric, p_cdf=p_cdf, ragged=True,
        use_kernel=use_kernel, interpret=interpret)
    return idx.astype(jnp.int32), probs, nv, tiers, diff, metrics


def score_candidates(feats: jax.Array, query_emb: jax.Array,
                     w1_t, w1_q, b1, w2, b2, *, use_kernels: bool,
                     interpret: bool, tile: int,
                     n_cand: Optional[jax.Array] = None) -> jax.Array:
    """[B, N, Dt] features + [B, Dq] queries -> [B, N] candidate logits
    (Pallas `triple_score` kernel or its XLA ref). Row-and-candidate
    local: safe to shard over both the request and candidate axes.
    ``n_cand`` ([B] real candidates) lets the kernel skip the tiles past
    each row's count, whose logits are then the kernel's fill; the XLA
    ref scores every slot. Either way the caller masks those slots."""
    if use_kernels:
        from repro.kernels.triple_score import kernel as ts_kernel
        return ts_kernel.triple_score_batched(
            feats, query_emb, w1_t, w1_q, b1, w2, b2, n_cand=n_cand,
            tile=tile, interpret=interpret)
    from repro.kernels.triple_score.ref import triple_score_batched_ref
    return triple_score_batched_ref(feats, query_emb, w1_t, w1_q, b1, w2, b2)


@functools.partial(jax.jit, static_argnames=("top_k", "metric", "p_cdf",
                                             "ragged", "use_kernels",
                                             "interpret", "tile"))
def _retrieved_program(feats: jax.Array, query_emb: jax.Array,
                       w1_t, w1_q, b1, w2, b2,
                       thresholds: jax.Array, n_cand: Optional[jax.Array],
                       *, top_k: int, metric: str, p_cdf: float,
                       ragged: bool, use_kernels: bool, interpret: bool,
                       tile: int):
    """The tentpole: scoring -> top-k -> skew metrics -> tier decision in
    ONE jitted device program. Candidate scores never leave HBM; the host
    sees only the [B, K] retrieval output and the [B] tier ids."""
    logits = score_candidates(feats, query_emb, w1_t, w1_q, b1, w2, b2,
                              use_kernels=use_kernels, interpret=interpret,
                              tile=tile, n_cand=n_cand if ragged else None)
    return topk_sigmoid_decision(
        logits, thresholds, n_cand, top_k=top_k, metric=metric,
        p_cdf=p_cdf, ragged=ragged, use_kernel=use_kernels,
        interpret=interpret)


def route_retrieved(feats: jax.Array, query_emb: jax.Array,
                    params: Mapping[str, jax.Array], config: RouterConfig,
                    n_cand: Optional[jax.Array] = None,
                    interpret: Optional[bool] = None,
                    use_kernels: bool = True,
                    tile: int = 128) -> RetrievedRouteResult:
    """Fused end-to-end routing: per-query candidate features in, tier
    decisions out, with zero host round-trips in between.

    ``feats``: [B, N, Dt] per-query candidate triple features (padded to a
    common N; see `repro.retrieval.scorer.batch_triple_features`).
    ``query_emb``: [B, Dq]. ``params``: the scorer weight dict — its
    layout (``w1_t``/``w1_q``/``b1``/``w2``/``b2``) is the Pallas
    `triple_score` kernel's argument order, making the kernel a drop-in.
    ``n_cand``: optional [B] real candidate counts (ragged retrieval);
    padded rows beyond ``n_cand`` are masked out of the top-k.
    ``use_kernels=False`` runs the identical chain on the XLA reference
    ops (the oracle variant — still one jitted program).

    ``interpret=None`` re-resolves compiled-vs-interpret at every call
    (`repro.kernels.device.default_interpret`), so a policy restored on a
    different host never replays the donor device's choice.
    """
    if interpret is None:
        interpret = default_interpret()
    k = min(config.top_k, feats.shape[1])
    idx, probs, nv, tiers, diff, metrics = _retrieved_program(
        feats, query_emb, params["w1_t"], params["w1_q"], params["b1"],
        params["w2"], params["b2"], _thresholds_array(config.thresholds),
        None if n_cand is None else jnp.asarray(n_cand, jnp.int32),
        top_k=k, metric=config.metric, p_cdf=config.cumulative_p,
        ragged=n_cand is not None, use_kernels=use_kernels,
        interpret=interpret, tile=tile)
    return RetrievedRouteResult(indices=idx, probs=probs, n_valid=nv,
                                tiers=tiers, difficulty=diff, metrics=metrics)


# -- end-to-end from the on-device knowledge graph ------------------------------

#: Leading int32 columns of a packed question-decision buffer: the
#: float32 decision of :func:`pack_decision` as its bits, then n_valid.
_KG_HEAD = N_METRICS + 2


def subgraph_hops(topics: jax.Array, heads: jax.Array, tails: jax.Array,
                  valid: jax.Array, max_hops: int):
    """Hop distances of each candidate triple's head and tail from the
    question's topic, over the question's own valid candidate triples as
    directed edges head -> tail: ``[B]`` topics, ``[B, N]`` entity ids and
    validity -> two ``[B, N]`` int32 arrays, exact for 0 .. max_hops - 1
    and ``max_hops`` for farther or unreachable.

    A node's distance is one more than the least distance of the head of
    an edge into it. Every node of a shortest path but the last is the
    head of a candidate triple, so ``max_hops - 1`` rounds over the heads
    make every head within ``max_hops - 1`` exact; one more round gives
    the tails. Nodes are matched by entity id, ``[B, N, N]`` comparisons
    a round."""
    top = topics[:, None]
    into_head = (tails[:, :, None] == heads[:, None, :]) & valid[:, :, None]
    into_tail = (tails[:, :, None] == tails[:, None, :]) & valid[:, :, None]

    def relax(dist, into, via):
        step = jnp.min(jnp.where(into, via[:, :, None] + 1, max_hops), axis=1)
        return jnp.minimum(dist, step)

    dh = jnp.where(heads == top, 0, max_hops).astype(jnp.int32)
    for _ in range(max_hops - 1):
        dh = relax(dh, into_head, dh)
    dt = jnp.where(tails == top, 0, max_hops).astype(jnp.int32)
    return dh, relax(dt, into_tail, dh)


def kg_features(ent, rel, heads, rels, tails, topics, query_emb, cand_ids,
                n_cand) -> jax.Array:
    """A question batch's candidate features, gathered on the device:
    ``[B, N]`` candidate triple ids (valid below ``n_cand``) -> ``[B, N,
    3d + 2(MAX_DDE_HOPS+1) + 4]``: head, relation and tail rows, the DDE
    one-hots of head and tail over the question's subgraph, and q.h, q.r,
    q.t, q.(h+r) over sqrt(d) at ``HIGHEST`` precision. A padded slot
    reads triple 0, so every gather stays in bounds; it is no edge of the
    subgraph, and the decision masks it out."""
    from repro.kernels.triple_score.kernel import PRECISION
    from repro.retrieval.scorer import MAX_DDE_HOPS

    n = cand_ids.shape[1]
    valid = jnp.arange(n, dtype=jnp.int32)[None, :] < n_cand[:, None]
    ids = jnp.where(valid, cand_ids, 0)
    hid, rid, tid = heads[ids], rels[ids], tails[ids]
    h, r, t = ent[hid], rel[rid], ent[tid]
    dh, dt = subgraph_hops(topics, hid, tid, valid, MAX_DDE_HOPS)
    eye = jnp.eye(MAX_DDE_HOPS + 1, dtype=jnp.float32)
    qv = query_emb.astype(jnp.float32) / np.sqrt(np.float32(ent.shape[1]))

    def sim(x):
        return jnp.einsum("bnd,bd->bn", x, qv, precision=PRECISION)

    sims = jnp.stack([sim(h), sim(r), sim(t), sim(h + r)], axis=-1)
    return jnp.concatenate([h, r, t, eye[dh], eye[dt], sims], axis=-1)


def pack_kg(tiers, metrics, n_valid, probs, triple_ids) -> jax.Array:
    """One ``[B, 6 + 2K]`` int32 buffer: the decision of
    :func:`pack_decision` and the top-K scores as their float32 bits,
    ``n_valid`` and the top-K triple ids as they are. Integer columns
    keep the ids exact at any graph size."""
    def bits(x):
        return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.concatenate(
        [bits(pack_decision(tiers, metrics)), n_valid[:, None], bits(probs),
         triple_ids], axis=-1)


def unpack_kg(buf: np.ndarray, metric: str):
    """Host inverse of :func:`pack_kg` on a read-back buffer: ``(tiers,
    difficulty, metrics, n_valid, probs, triple_ids)``."""
    k = (buf.shape[1] - _KG_HEAD) // 2
    floats = buf.view(np.float32)
    tiers, diff, metrics = unpack_decision(floats[:, :N_METRICS + 1], metric)
    return (tiers, diff, metrics, buf[:, N_METRICS + 1].copy(),
            floats[:, _KG_HEAD:_KG_HEAD + k], buf[:, _KG_HEAD + k:])


@functools.partial(jax.jit, static_argnames=("top_k", "metric", "p_cdf",
                                             "use_kernels", "interpret",
                                             "tile"))
def _kg_program(ent, rel, heads, rels, tails, w1_t, w1_q, b1, w2, b2,
                thresholds, topics, query_emb, cand_ids, n_cand, *,
                top_k: int, metric: str, p_cdf: float, use_kernels: bool,
                interpret: bool, tile: int) -> jax.Array:
    """Candidate triple ids -> features from the on-device tables ->
    triple scores -> top-k -> skew metrics -> tiers, and the top-k triple
    ids, as ONE jitted program with ONE output (:func:`pack_kg`)."""
    feats = kg_features(ent, rel, heads, rels, tails, topics, query_emb,
                        cand_ids, n_cand)
    logits = score_candidates(feats, query_emb, w1_t, w1_q, b1, w2, b2,
                              use_kernels=use_kernels, interpret=interpret,
                              tile=tile, n_cand=n_cand)
    idx, probs, nv, tiers, _, metrics = topk_sigmoid_decision(
        logits, thresholds, n_cand, top_k=top_k, metric=metric, p_cdf=p_cdf,
        ragged=True, use_kernel=use_kernels, interpret=interpret)
    ids = jnp.take_along_axis(cand_ids, idx, axis=1)
    ids = jnp.where(jnp.arange(top_k)[None, :] < nv[:, None], ids, -1)
    return pack_kg(tiers, metrics, nv, probs, ids)


def route_kg(store, params: Mapping[str, jax.Array], config: RouterConfig,
             topics: jax.Array, query_emb: jax.Array, cand_ids: jax.Array,
             n_cand: jax.Array, interpret: Optional[bool] = None,
             use_kernels: bool = True, tile: int = 128) -> jax.Array:
    """Questions in, decisions and top-K triple ids out, from a
    :class:`repro.retrieval.store.DeviceKG`: ``topics`` [B] and
    ``cand_ids`` [B, N] int32, ``query_emb`` [B, d], ``n_cand`` [B] real
    candidates (1 .. N; 0 marks a padding row, whose tiles the scorer
    kernel skips and whose output is no decision). Returns the packed
    buffer (:func:`unpack_kg`). ``use_kernels=False`` runs the same
    program on the XLA reference ops.
    """
    if interpret is None:
        interpret = default_interpret()
    return _kg_program(
        store.ent, store.rel, store.heads, store.rels, store.tails,
        params["w1_t"], params["w1_q"], params["b1"], params["w2"],
        params["b2"], _thresholds_array(config.thresholds), topics,
        query_emb, cand_ids, n_cand,
        top_k=min(config.top_k, cand_ids.shape[1]), metric=config.metric,
        p_cdf=config.cumulative_p, use_kernels=use_kernels,
        interpret=interpret, tile=tile)


def route_retrieved_staged(feats, query_emb, params: Mapping,
                           config: RouterConfig,
                           n_cand=None) -> RetrievedRouteResult:
    """The readable host-staged reference for :func:`route_retrieved` —
    exactly what the pre-fusion serving path did per request: XLA scoring,
    scores back to host, numpy argsort top-k, sigmoid, then the oracle
    skew metrics and threshold compare. Used by the parity tests and as
    the end-to-end benchmark baseline; never the serving path.
    """
    import numpy as np

    from repro.kernels.skew_metrics.ref import (mask_from_n_valid,
                                                skew_metrics_ref)
    from repro.kernels.triple_score.ref import triple_score_ref

    feats = np.asarray(feats)
    query_emb = np.asarray(query_emb)
    b, n, _ = feats.shape
    k = min(config.top_k, n)
    nc = (np.full(b, n, np.int32) if n_cand is None
          else np.clip(np.asarray(n_cand, np.int32), 1, n))
    idx = np.zeros((b, k), np.int32)
    probs = np.zeros((b, k), np.float32)
    nv = np.minimum(nc, k).astype(np.int32)
    for i in range(b):
        scores = np.asarray(triple_score_ref(
            jnp.asarray(feats[i, :nc[i]]), jnp.asarray(query_emb[i][None]),
            params["w1_t"], params["w1_q"], params["b1"],
            params["w2"], params["b2"]))[0]
        order = np.argsort(-scores, kind="stable")[:k]
        idx[i, :len(order)] = order
        probs[i, :len(order)] = 1.0 / (1.0 + np.exp(-scores[order]))
    mask = mask_from_n_valid(jnp.asarray(nv), k)
    metrics = skew_metrics_ref(jnp.asarray(probs), p_cdf=config.cumulative_p,
                               mask=mask)
    diff = difficulty_from_metrics(metrics, config.metric)
    tiers = route_from_difficulty(diff, jnp.asarray(config.thresholds))
    return RetrievedRouteResult(indices=jnp.asarray(idx),
                                probs=jnp.asarray(probs),
                                n_valid=jnp.asarray(nv), tiers=tiers,
                                difficulty=diff, metrics=metrics)


@dataclasses.dataclass(frozen=True)
class RoutingStats:
    """Aggregate telemetry for a routed batch (exported by the dispatcher)."""

    tier_counts: tuple[int, ...]
    large_call_ratio: float  # fraction sent to the top tier
    mean_difficulty: float

    @staticmethod
    def from_assignments(tiers: jax.Array, n_tiers: int,
                         difficulty: jax.Array) -> "RoutingStats":
        counts = tuple(int(jnp.sum(tiers == t)) for t in range(n_tiers))
        n = max(int(tiers.size), 1)
        return RoutingStats(
            tier_counts=counts,
            large_call_ratio=counts[-1] / n,
            mean_difficulty=float(jnp.mean(difficulty)),
        )


def expected_tier_shares(difficulty: jax.Array,
                         thresholds: Sequence[float]) -> list[float]:
    """Empirical share of traffic per tier for a difficulty sample."""
    tiers = route_from_difficulty(difficulty, jnp.asarray(tuple(thresholds)))
    n = max(int(tiers.size), 1)
    return [float(jnp.sum(tiers == t)) / n for t in range(len(tuple(thresholds)) + 1)]
