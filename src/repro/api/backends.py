"""Pluggable difficulty backends: ONE place that decides how skew metrics
are computed. A :class:`DifficultyBackend` is a named, swappable policy
object:

* ``oracle`` — the readable XLA path (`repro.core.skewness`, via the
  kernel's stacked ref), still fused into ONE jitted decision program
  per batch. Ground truth, and the fastest path at small batch sizes,
  where Pallas launch/interpret overhead dominates.
* ``pallas`` / ``fused`` — two names of one kernel backend
  (:class:`PallasBackend`): the fused single-pass skew kernel
  (`repro.kernels.skew_metrics`) for scores in, and the end-to-end
  program (`triple_score` Pallas scoring -> device top-k -> skew kernel
  -> threshold decision, one jitted computation) for candidate features
  in (:meth:`route_retrieved`) and for questions over a knowledge graph
  on the device (:meth:`route_kg`). Interpret mode off-TPU.
* ``auto``   — the production policy: a measured BATCH-SIZE CROSSOVER.
  Batches below ``crossover_batch`` go to the ``oracle`` program, batches
  at or above it to the ``fused`` kernels. The crossover is a
  serializable :class:`~repro.api.spec.RouteSpec` field so every replica
  agrees.
* ``sharded`` — the mesh-sharded dispatch path (`repro.api.sharded`).

Interpret-vs-compiled is NEVER stored: every backend defers to
:func:`repro.kernels.device.default_interpret` at CALL time (compiled on
TPU, interpret elsewhere), so snapshots restored on a different host
re-resolve against the local devices.

Every backend produces the SAME contract: ``[B, K]`` descending-sorted
scores (+ optional ``[B]`` ``n_valid``) -> a full
:class:`~repro.core.router.RouteBatchResult` with the raw ``[B, 4]``
metric matrix in kernel column order, so the configured metric is always
a column select — never a recompile — regardless of backend. Other
backends register with :func:`register_backend` and become selectable
from a :class:`~repro.api.spec.RouteSpec` by name.

Backends are POLICY-AGNOSTIC: they produce the threshold-tier ids plus
the raw metric matrix, and the dispatcher's routing policy
(`repro.policies`) transforms that decision host-side afterwards, so
every policy works identically under every backend.
"""

from __future__ import annotations

import functools
from typing import Callable, Mapping, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.router import (RetrievedRouteResult, RouteBatchResult,
                               RouterConfig, route_all_metrics, route_kg,
                               route_retrieved)
from repro.kernels.device import default_interpret  # noqa: F401  (re-export)

# Measured on the BENCH_routing_fastpath.json CPU-interpret grid: the
# fused kernel path loses to the single-program oracle below ~32 rows
# (0.25–0.72x at B=1) and wins decisively from B=64 up (18–79x). On TPU
# the compiled kernel wins earlier — deployments set the spec field.
DEFAULT_CROSSOVER_BATCH = 32


def _as_rows(scores_desc) -> jax.Array:
    """Score rows as a ``[B, K]`` array without a device program: a 2-D
    array passes through as it is (a device array stays where it is),
    and only a single ``[K]`` row is lifted to ``[1, K]``, on the host."""
    if np.ndim(scores_desc) == 2:
        return jnp.asarray(scores_desc)
    return jnp.asarray(np.atleast_2d(np.asarray(scores_desc)))


@runtime_checkable
class DifficultyBackend(Protocol):
    """Computes skew metrics + tier assignments for score batches."""

    name: str

    def metrics(self, scores_desc: jax.Array,
                p_cdf: float = 0.95,
                n_valid: Optional[jax.Array] = None) -> jax.Array:
        """[B, K] descending scores -> [B, 4] raw metrics (kernel order)."""
        ...

    def route_batch(self, scores_desc: jax.Array, config: RouterConfig,
                    n_valid: Optional[jax.Array] = None) -> RouteBatchResult:
        """[B, K] -> tiers/difficulty/metrics under ``config``."""
        ...


class _SingleProgramBackend:
    """Shared machinery: both concrete backends run the whole
    metrics -> column-select -> threshold decision as ONE jitted device
    program (`core.router._decision_program`); they differ only in the
    metric implementation traced into it (``_use_kernel``) and in which
    scoring stage :meth:`route_retrieved` fuses in front.

    ``interpret=None`` defers to :func:`default_interpret` at call time,
    so a backend object built off-TPU keeps working if devices change.
    """

    _use_kernel: bool

    def __init__(self, interpret: Optional[bool] = None):
        self.interpret = interpret

    def effective_interpret(self) -> bool:
        """The interpret mode this call would use — resolved NOW, never
        replayed from construction or snapshot time."""
        return default_interpret() if self.interpret is None \
            else self.interpret

    def metrics(self, scores_desc, p_cdf: float = 0.95, n_valid=None):
        return self.route_batch(
            scores_desc,
            RouterConfig(metric="gini", thresholds=(0.0,),
                         cumulative_p=p_cdf), n_valid=n_valid).metrics

    def route_batch(self, scores_desc, config: RouterConfig, n_valid=None):
        return route_all_metrics(
            _as_rows(scores_desc), config,
            n_valid=None if n_valid is None else jnp.asarray(n_valid),
            interpret=self.effective_interpret(),
            use_kernel=self._use_kernel)

    def route_retrieved(self, feats, query_emb, params: Mapping,
                        config: RouterConfig,
                        n_cand=None) -> RetrievedRouteResult:
        """[B, N, Dt] candidate features + [B, Dq] queries -> full
        retrieve-to-decision output in one jitted program.

        Off-TPU the Pallas stages would run under the interpreter — a
        correctness tool that loses to plain XLA by >3x on the scoring
        MLP (measured: e2e B=64 cell at 0.3x before this fallback) — so
        when the call resolves to interpret mode the SAME fused program
        is traced from the XLA implementations instead. On TPU
        (interpret False) the real kernels run.
        """
        interp = self.effective_interpret()
        return route_retrieved(
            jnp.asarray(feats), jnp.asarray(query_emb), params, config,
            n_cand=None if n_cand is None else jnp.asarray(n_cand),
            interpret=interp,
            use_kernels=self._use_kernel and not interp)

    def route_kg(self, store, params: Mapping, config: RouterConfig,
                 topics, query_emb, cand_ids, n_cand) -> jax.Array:
        """Questions -> features gathered from ``store`` (a
        `repro.retrieval.store.DeviceKG`) -> scoring -> top-k -> decision,
        one jitted program returning one packed buffer
        (`repro.core.router.route_kg`); off-TPU on the XLA ops, as
        :meth:`route_retrieved`."""
        interp = self.effective_interpret()
        return route_kg(store, params, config, topics, query_emb, cand_ids,
                        n_cand, interpret=interp,
                        use_kernels=self._use_kernel and not interp)


class OracleBackend(_SingleProgramBackend):
    """XLA ground-truth backend (`core.skewness` metrics, stacked) — one
    jitted program per batch, no Pallas launch: the small-batch winner."""

    name = "oracle"
    _use_kernel = False

    def __init__(self):
        super().__init__(interpret=None)


class PallasBackend(_SingleProgramBackend):
    """The kernel backend: the fused skew kernel (`kernels.skew_metrics`)
    behind every entry, and `triple_score` scoring in front of it in
    :meth:`route_retrieved` and :meth:`route_kg`. Registered as ``pallas``
    and as ``fused``; ``name`` is the one it was made under."""

    _use_kernel = True

    def __init__(self, interpret: Optional[bool] = None,
                 name: str = "pallas"):
        super().__init__(interpret)
        self.name = name


class AutoBackend:
    """Batch-size crossover policy: ``oracle`` below ``crossover_batch``,
    the ``fused`` kernels at or above it. The crossover is policy, not
    environment — it lives in :class:`~repro.api.spec.RouteSpec` so
    replicas agree — while the interpret-vs-compiled choice stays
    call-time per host.
    """

    name = "auto"

    def __init__(self, crossover_batch: int = DEFAULT_CROSSOVER_BATCH,
                 interpret: Optional[bool] = None):
        if crossover_batch < 1:
            raise ValueError(f"crossover_batch must be >= 1, "
                             f"got {crossover_batch}")
        self.crossover_batch = int(crossover_batch)
        self.oracle = OracleBackend()
        self.fused = PallasBackend(interpret=interpret, name="fused")
        # crossover-pick counters; replaced with live instruments when a
        # session attaches its observability plane (attach_obs)
        from repro.obs.registry import NULL_INSTRUMENT
        self._m_pick = {"oracle": NULL_INSTRUMENT, "fused": NULL_INSTRUMENT}

    def attach_obs(self, obs) -> None:
        """Wire the session's observability plane in: which side of the
        batch-size crossover each dispatch lands on becomes a counter
        (``backend_pick_total{path=oracle|fused}``)."""
        self._m_pick = {
            path: obs.metrics.counter("backend_pick_total", path=path)
            for path in ("oracle", "fused")}

    @property
    def interpret(self) -> Optional[bool]:
        return self.fused.interpret

    def effective_interpret(self) -> bool:
        return self.fused.effective_interpret()

    def pick(self, batch_size: int) -> DifficultyBackend:
        """The crossover in one place (bench/telemetry introspect this)."""
        side = self.oracle if batch_size < self.crossover_batch \
            else self.fused
        self._m_pick["oracle" if side is self.oracle else "fused"].inc()
        return side

    def metrics(self, scores_desc, p_cdf: float = 0.95, n_valid=None):
        scores = _as_rows(scores_desc)
        return self.pick(scores.shape[0]).metrics(scores, p_cdf=p_cdf,
                                                  n_valid=n_valid)

    def route_batch(self, scores_desc, config: RouterConfig, n_valid=None):
        scores = _as_rows(scores_desc)
        return self.pick(scores.shape[0]).route_batch(scores, config,
                                                      n_valid=n_valid)

    def route_retrieved(self, feats, query_emb, params: Mapping,
                        config: RouterConfig, n_cand=None):
        return self.pick(jnp.asarray(feats).shape[0]).route_retrieved(
            feats, query_emb, params, config, n_cand=n_cand)

    def route_kg(self, store, params: Mapping, config: RouterConfig,
                 topics, query_emb, cand_ids, n_cand):
        return self.pick(cand_ids.shape[0]).route_kg(
            store, params, config, topics, query_emb, cand_ids, n_cand)


# --- registry ----------------------------------------------------------------

_REGISTRY: dict[str, Callable[..., DifficultyBackend]] = {}


def register_backend(name: str,
                     factory: Callable[..., DifficultyBackend]) -> None:
    """Register a backend factory under ``name`` (RouteSpec-selectable)."""
    if not name or name == "auto":
        raise ValueError(f"invalid backend name {name!r}")
    _REGISTRY[name] = factory


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY)) + ("auto",)


def make_backend(name: str = "auto", **kwargs) -> DifficultyBackend:
    """Instantiate a difficulty backend by name (``auto`` = the batch-size
    crossover over oracle/fused — see module docstring; accepts
    ``crossover_batch=``)."""
    if name == "auto":
        return AutoBackend(**kwargs)
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown difficulty backend {name!r}; "
                         f"choose from {available_backends()}") from None
    return factory(**kwargs)


def _make_sharded(**kwargs) -> DifficultyBackend:
    """Lazy factory: the mesh-sharded dispatch backend (`api/sharded.py`)
    — imported on first use so merely listing backends never touches
    device state. Accepts ``crossover_batch=``/``mesh=``."""
    from repro.api.sharded import ShardedBackend
    return ShardedBackend(**kwargs)


register_backend("oracle", OracleBackend)
register_backend("pallas", PallasBackend)
register_backend("fused", functools.partial(PallasBackend, name="fused"))
register_backend("sharded", _make_sharded)
