"""Mesh-sharded dispatch: the ``sharded`` difficulty backend.

The routing decision is embarrassingly request-parallel — every row's
skew metrics depend only on that row's top-K scores — so the
millions-of-users fan-out is a textbook ``shard_map``: split the
dispatch batch over the mesh's data axes (the logical ``"request"``
axis from `distributed/sharding.py`), run the SAME fused
retrieve-to-decision program per shard, and concatenate the tier ids.
Candidate scoring additionally shards the ``"candidate"`` axis (the
rules-table entry that sat unused since the sharding layer landed) over
the model axis, with one tiled ``all_gather`` reassembling the per-shard
logits before the global top-k.

Parity with the ``auto`` backend is bit-for-bit BY CONSTRUCTION, not by
tolerance:

* the oracle-vs-fused crossover is decided on the GLOBAL batch size
  (the wrapped :class:`~repro.api.backends.AutoBackend` picks), so a
  B=8 batch routes through the oracle program on every shard exactly as
  ``auto`` would route it unsharded;
* each shard runs the identical jitted programs
  (`core.router._decision_program` / `score_candidates` +
  `topk_sigmoid_decision`) on its contiguous row block — row-local
  float math, no cross-row reductions, no re-associated sums;
* per-shard bucket padding follows the dispatcher's convention (padded
  rows are well-defined garbage, sliced off on the way out).

The mesh is ENVIRONMENT, not policy: like interpret-vs-compiled it is
resolved at construction from the local devices and never serialized —
a `RouteSpec(backend="sharded")` restored on a 1-device host runs the
same program on a degenerate mesh and produces the same decisions.

Routing policies (`repro.policies`) compose transparently: the sharded
program emits the same threshold tiers/difficulty/metrics contract as
``auto``, and the policy transform (cascade escalation, depth pick,
mode pricing) runs on the gathered host-side result — so e.g. a
cascade spec routes bit-for-bit identically under ``sharded`` and
``auto`` (asserted in tests/test_sharded_backend.py).
"""

from __future__ import annotations

from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.api.backends import AutoBackend, DEFAULT_CROSSOVER_BATCH
from repro.core.router import (RetrievedRouteResult, RouteBatchResult,
                               RouterConfig, _decision_program,
                               _thresholds_array, score_candidates,
                               topk_sigmoid_decision)
from repro.distributed import sharding as shd
from repro.launch.mesh import make_auto_mesh
from repro.serving.scheduler import bucket_size

#: Per-SHARD batch buckets. Smaller than the dispatcher's global buckets
#: (8..4096): with R shards a global 1024-row batch is 128 rows each, and
#: a 1-bucket keeps the degenerate tiny-batch case from padding 8x.
SHARD_BUCKETS = (1, 8, 64, 256, 1024)


def make_dispatch_mesh(n_request: Optional[int] = None,
                       n_candidate: int = 1) -> Mesh:
    """A (data=n_request, model=n_candidate) mesh for sharded dispatch.

    ``n_request=None`` takes every local device not claimed by the
    candidate axis — the serving default (CI forces 8 host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``). Under
    `DEFAULT_RULES` the logical ``"request"`` axis lands on ``data`` and
    ``"candidate"`` on ``model``.
    """
    if n_candidate < 1:
        raise ValueError(f"n_candidate must be >= 1, got {n_candidate}")
    n_dev = jax.local_device_count()
    if n_request is None:
        n_request = max(1, n_dev // n_candidate)
    if n_request * n_candidate > n_dev:
        raise ValueError(
            f"dispatch mesh ({n_request} request x {n_candidate} "
            f"candidate) wants {n_request * n_candidate} devices but only "
            f"{n_dev} are visible")
    return make_auto_mesh((n_request, n_candidate), ("data", "model"))


def _dim(mesh: Mesh, axis) -> int:
    return shd._axis_size(mesh, axis)


class ShardedBackend:
    """Mesh-parallel dispatch over the logical ``request``/``candidate``
    axes — ``auto``'s crossover policy, fanned out with ``shard_map``.

    ``mesh=None`` builds the full-host dispatch mesh lazily on first
    use, so constructing the backend (e.g. during spec validation or
    ``available_backends()``) never touches device state.
    """

    name = "sharded"

    def __init__(self, crossover_batch: int = DEFAULT_CROSSOVER_BATCH,
                 interpret: Optional[bool] = None,
                 mesh: Optional[Mesh] = None):
        self.auto = AutoBackend(crossover_batch=crossover_batch,
                                interpret=interpret)
        self._mesh = mesh
        self._programs: dict[tuple, object] = {}

    def attach_obs(self, obs) -> None:
        """Crossover-pick counters live on the inner ``auto`` (small
        batches take its oracle path; sharded programs count as fused)."""
        self.auto.attach_obs(obs)

    # -- mesh plumbing --------------------------------------------------------

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            self._mesh = make_dispatch_mesh()
        return self._mesh

    @property
    def crossover_batch(self) -> int:
        return self.auto.crossover_batch

    @property
    def interpret(self) -> Optional[bool]:
        return self.auto.interpret

    def effective_interpret(self) -> bool:
        return self.auto.effective_interpret()

    def _specs(self) -> tuple[P, P, P, int, int]:
        """(row, vec, feat) PartitionSpecs + (request, candidate) sizes
        under the logical rules, resolved against this backend's mesh."""
        mesh = self.mesh
        with shd.use_mesh(mesh):
            row = shd.spec_for("request", None)          # [B, K] blocks
            vec = shd.spec_for("request")                # [B] blocks
            feat = shd.spec_for("request", "candidate", None)  # [B, N, D]
        r = _dim(mesh, shd.DEFAULT_RULES["request"])
        c = _dim(mesh, shd.DEFAULT_RULES["candidate"])
        return row, vec, feat, r, c

    def _pad_rows(self, b: int, r: int) -> int:
        """Global padded batch: every shard gets the same bucketed block."""
        return bucket_size(-(-b // r), SHARD_BUCKETS) * r

    # -- the DifficultyBackend contract ---------------------------------------

    def metrics(self, scores_desc, p_cdf: float = 0.95, n_valid=None):
        return self.route_batch(
            scores_desc,
            RouterConfig(metric="gini", thresholds=(0.0,),
                         cumulative_p=p_cdf), n_valid=n_valid).metrics

    def route_batch(self, scores_desc, config: RouterConfig, n_valid=None):
        scores = jnp.atleast_2d(jnp.asarray(scores_desc))
        b, k = scores.shape
        use_kernel = self.auto.pick(b)._use_kernel  # GLOBAL-size crossover
        interpret = self.effective_interpret()
        row, vec, _, r, _ = self._specs()
        bpad = self._pad_rows(b, r)
        ragged = n_valid is not None
        if ragged:
            nv = np.full(bpad, k, np.int32)
            nv[:b] = np.asarray(n_valid, np.int32)
            nv[b:] = 1  # padded rows: degenerate but well-defined
        if bpad != b:
            scores = jnp.concatenate(
                [scores, jnp.zeros((bpad - b, k), scores.dtype)])
        prog = self._batch_program(config.metric, config.cumulative_p,
                                   ragged, use_kernel, interpret, row, vec)
        thr = _thresholds_array(config.thresholds)
        if ragged:
            decision = prog(scores, jnp.asarray(nv), thr)
        else:
            decision = prog(scores, thr)
        return RouteBatchResult(decision=decision[:b], metric=config.metric)

    def route_retrieved(self, feats, query_emb, params: Mapping,
                        config: RouterConfig,
                        n_cand=None) -> RetrievedRouteResult:
        feats = jnp.asarray(feats)
        qemb = jnp.asarray(query_emb)
        b, n, _ = feats.shape
        interp = self.effective_interpret()
        # same fallback as the auto/fused path: interpret-mode Pallas
        # loses to plain XLA on the scoring MLP, so off-TPU the fused
        # program traces the XLA implementations
        use_kernels = self.auto.pick(b)._use_kernel and not interp
        row, vec, feat, r, c = self._specs()
        # candidate-axis sharding needs an even split; otherwise the
        # candidate dim stays replicated (request-only parallelism)
        shard_cand = c > 1 and n % c == 0
        if not shard_cand:
            feat = P(feat[0], None, None)
        bpad = self._pad_rows(b, r)
        ragged = n_cand is not None
        if ragged:
            nc = np.full(bpad, n, np.int32)
            nc[:b] = np.asarray(n_cand, np.int32)
            nc[b:] = 1
        if bpad != b:
            feats = jnp.concatenate(
                [feats, jnp.zeros((bpad - b,) + feats.shape[1:],
                                  feats.dtype)])
            qemb = jnp.concatenate(
                [qemb, jnp.zeros((bpad - b, qemb.shape[1]), qemb.dtype)])
        k = min(config.top_k, n)
        prog = self._retrieved_prog(config.metric, config.cumulative_p, k,
                                    ragged, use_kernels, interp, shard_cand,
                                    row, vec, feat)
        thr = _thresholds_array(config.thresholds)
        args = (feats, qemb, params["w1_t"], params["w1_q"], params["b1"],
                params["w2"], params["b2"])
        if ragged:
            out = prog(*args, jnp.asarray(nc), thr)
        else:
            out = prog(*args, thr)
        idx, probs, nv, tiers, diff, metrics = out
        return RetrievedRouteResult(
            indices=idx[:b], probs=probs[:b], n_valid=nv[:b],
            tiers=tiers[:b], difficulty=diff[:b], metrics=metrics[:b])

    # -- cached shard_map programs --------------------------------------------
    # Replication checking is off (check_vma=False): the outputs are
    # row-local or all-gathered, but the varying-manual-axes checker
    # cannot see through the gathered top-k.

    def _batch_program(self, metric: str, p_cdf: float, ragged: bool,
                       use_kernel: bool, interpret: bool, row: P, vec: P):
        key = ("batch", metric, p_cdf, ragged, use_kernel, interpret)
        prog = self._programs.get(key)
        if prog is not None:
            return prog

        def body_ragged(scores_s, nv_s, thr):
            return _decision_program(
                scores_s, thr, nv_s, metric=metric, p_cdf=p_cdf,
                ragged=True, use_kernel=use_kernel, interpret=interpret)

        def body_dense(scores_s, thr):
            return _decision_program(
                scores_s, thr, None, metric=metric, p_cdf=p_cdf,
                ragged=False, use_kernel=use_kernel, interpret=interpret)

        in_specs = (row, vec, P()) if ragged else (row, P())
        prog = jax.jit(jax.shard_map(
            body_ragged if ragged else body_dense, mesh=self.mesh,
            in_specs=in_specs, out_specs=row, check_vma=False))
        self._programs[key] = prog
        return prog

    def _retrieved_prog(self, metric: str, p_cdf: float, top_k: int,
                        ragged: bool, use_kernels: bool, interpret: bool,
                        shard_cand: bool, row: P, vec: P, feat: P,
                        tile: int = 128):
        key = ("retrieved", metric, p_cdf, top_k, ragged, use_kernels,
               interpret, shard_cand)
        prog = self._programs.get(key)
        if prog is not None:
            return prog

        def tail(logits, nc_s, thr):
            if shard_cand:  # reassemble the candidate axis for global top-k
                logits = jax.lax.all_gather(logits, "model", axis=1,
                                            tiled=True)
            return topk_sigmoid_decision(
                logits, thr, nc_s, top_k=top_k, metric=metric, p_cdf=p_cdf,
                ragged=ragged, use_kernel=use_kernels, interpret=interpret)

        def body_ragged(feats_s, qemb_s, w1_t, w1_q, b1, w2, b2, nc_s, thr):
            logits = score_candidates(
                feats_s, qemb_s, w1_t, w1_q, b1, w2, b2,
                use_kernels=use_kernels, interpret=interpret, tile=tile)
            return tail(logits, nc_s, thr)

        def body_dense(feats_s, qemb_s, w1_t, w1_q, b1, w2, b2, thr):
            logits = score_candidates(
                feats_s, qemb_s, w1_t, w1_q, b1, w2, b2,
                use_kernels=use_kernels, interpret=interpret, tile=tile)
            return tail(logits, None, thr)

        qspec = P(row[0], None)
        params = (P(),) * 5
        in_specs = ((feat, qspec) + params + ((vec, P()) if ragged
                                             else (P(),)))
        out_specs = (row, row, vec, vec, vec, row)
        prog = jax.jit(jax.shard_map(
            body_ragged if ragged else body_dense, mesh=self.mesh,
            in_specs=in_specs, out_specs=out_specs, check_vma=False))
        self._programs[key] = prog
        return prog
