"""`repro.api` surface tests: spec serialization, session equivalence,
snapshot/restore, backend registry, deprecation shims."""

import dataclasses
import json

import numpy as np
import pytest

from repro.api import (CalibrationSpec, OracleBackend, PallasBackend,
                       RouteSpec, SkewRouteSession, available_backends,
                       build, make_backend, register_backend)
from repro.api import backends as backends_mod
from repro.core import RouterConfig
from repro.serving import _deprecation
from repro.serving.pipeline import ServingPipeline
from repro.serving.router_service import SkewRouteDispatcher


def _desc_scores(b, k, seed=0):
    rng = np.random.default_rng(seed)
    return np.sort(rng.uniform(0.01, 1, (b, k)).astype(np.float32),
                   axis=1)[:, ::-1].copy()


def _three_tier_spec(scores, backend="auto", **overrides):
    """Thresholds at the 50/80% difficulty quantiles -> non-trivial mix."""
    diff = np.asarray(OracleBackend().route_batch(
        scores, RouterConfig(metric="entropy", thresholds=(0.0,))).difficulty)
    t0, t1 = np.quantile(diff, [0.5, 0.8])
    return RouteSpec(metric="entropy", thresholds=(float(t0), float(t1)),
                     tier_names=("qwen7b", "qwen14b", "qwen72b"),
                     top_k=scores.shape[1], backend=backend, **overrides)


# -- RouteSpec serialization --------------------------------------------------

def test_spec_json_roundtrip_identity():
    from repro.api import CostSpec
    spec = RouteSpec(
        metric="cumulative", thresholds=(3.0, 7.5), cumulative_p=0.9,
        top_k=50, tier_names=("s", "m", "l"),
        tier_models=("qwen7b", "qwen14b", "qwen72b"),
        backend="oracle", micro_batch=16,
        cost=CostSpec(cost_per_mtok={"qwen7b": 0.5, "qwen14b": 1.0,
                                     "qwen72b": 5.0}),
        calibration=CalibrationSpec(policy="streaming",
                                    target_shares=(0.5, 0.3, 0.2),
                                    window=512, min_samples=32,
                                    tolerance=0.1, cooldown=64))
    again = RouteSpec.from_json(spec.to_json())
    assert again == spec
    assert hash(again) == hash(spec)  # frozen policy values stay hashable
    assert again.cost_model().cost_per_mtok["qwen72b"] == 5.0
    # json payload is pure data (no Python reprs)
    payload = json.loads(spec.to_json())
    assert payload["schema_version"] == 1
    assert payload["calibration"]["target_shares"] == [0.5, 0.3, 0.2]


def test_spec_validation_inherits_router_checks():
    with pytest.raises(ValueError, match="unknown metric"):
        RouteSpec(metric="nope")
    with pytest.raises(ValueError, match="top_k must be >= 1"):
        RouteSpec(top_k=0)
    with pytest.raises(ValueError, match=r"cumulative_p must be in \(0, 1\]"):
        RouteSpec(cumulative_p=1.5)
    with pytest.raises(ValueError, match="ascending"):
        RouteSpec(thresholds=(2.0, 1.0), tier_names=("a", "b", "c"))


def test_spec_validation_spec_level():
    with pytest.raises(ValueError, match="tier_names"):
        RouteSpec(thresholds=(0.0,), tier_names=("only-one",))
    with pytest.raises(ValueError, match="tier_models"):
        RouteSpec(tier_models=("just-one",))
    with pytest.raises(ValueError, match="unknown difficulty backend"):
        RouteSpec(backend="quantum")
    with pytest.raises(ValueError, match="micro_batch"):
        RouteSpec(micro_batch=0)
    with pytest.raises(ValueError, match="target_shares"):
        CalibrationSpec(policy="streaming")
    with pytest.raises(ValueError, match="sum to 1"):
        CalibrationSpec(policy="streaming", target_shares=(0.9, 0.9))
    with pytest.raises(ValueError, match="unknown calibration policy"):
        CalibrationSpec(policy="sometimes")
    with pytest.raises(ValueError, match="window must be >= 2"):
        CalibrationSpec(window=1)
    with pytest.raises(ValueError, match="min_samples must be >= 2"):
        CalibrationSpec(min_samples=1)
    with pytest.raises(ValueError, match="can never be reached"):
        CalibrationSpec(window=64, min_samples=256)
    with pytest.raises(ValueError, match=r"tolerance must be in \(0, 1\)"):
        CalibrationSpec(tolerance=0.0)
    with pytest.raises(ValueError, match="cooldown must be >= 0"):
        CalibrationSpec(cooldown=-1)
    with pytest.raises(ValueError, match="calibration target_shares"):
        RouteSpec(calibration=CalibrationSpec(
            policy="streaming", target_shares=(0.5, 0.3, 0.2)))


def test_spec_from_dict_rejects_unknown_and_versioned():
    base = RouteSpec().to_dict()
    with pytest.raises(ValueError, match="schema_version"):
        RouteSpec.from_dict({**base, "schema_version": 99})
    with pytest.raises(ValueError, match="unknown RouteSpec fields"):
        RouteSpec.from_dict({**base, "surprise": 1})
    with pytest.raises(ValueError, match="unknown CalibrationSpec fields"):
        RouteSpec.from_dict(
            {**base, "calibration": {"policy": "static", "wat": 2}})


def test_router_config_validation_messages():
    with pytest.raises(ValueError, match="top_k must be >= 1, got 0"):
        RouterConfig(top_k=0)
    with pytest.raises(ValueError, match=r"cumulative_p must be in \(0, 1\], "
                                         r"got 0.0"):
        RouterConfig(cumulative_p=0.0)
    with pytest.raises(ValueError, match="got 1.5"):
        RouterConfig(cumulative_p=1.5)
    assert RouterConfig(cumulative_p=1.0).cumulative_p == 1.0  # closed top


# -- acceptance: json round-trip rebuilds an equivalent session ---------------

@pytest.mark.parametrize("backend", ["oracle", "pallas"])
def test_roundtrip_session_equivalence_b1024(backend):
    scores = _desc_scores(1024, 100)
    spec = _three_tier_spec(scores, backend=backend)
    session = build(spec)
    rebuilt = build(RouteSpec.from_json(spec.to_json()))
    a = session.route(scores)
    b = rebuilt.route(scores)
    assert np.array_equal(a.tiers, b.tiers)
    np.testing.assert_array_equal(a.difficulty, b.difficulty)
    # the mix is non-trivial (all three tiers hit)
    assert len(set(a.tiers.tolist())) == 3


def test_backends_agree_on_tiers():
    scores = _desc_scores(256, 64, seed=3)
    n_valid = np.random.default_rng(4).integers(5, 64, 256).astype(np.int32)
    spec_o = _three_tier_spec(scores, backend="oracle")
    spec_p = dataclasses.replace(spec_o, backend="pallas")
    to = build(spec_o).route(scores, n_valid=n_valid)
    tp = build(spec_p).route(scores, n_valid=n_valid)
    assert np.array_equal(to.tiers, tp.tiers)


# -- satellite: single-request dispatch is the batched path -------------------

@pytest.mark.parametrize("backend", ["oracle", "pallas"])
def test_route_one_matches_batch(backend):
    scores = _desc_scores(16, 50, seed=1)
    spec = _three_tier_spec(scores, backend=backend)
    batch_tiers = build(spec).route(scores).tiers
    singles = build(spec)
    for i in range(scores.shape[0]):
        rec = singles.route_one(scores[i])
        assert rec.tier == int(batch_tiers[i])


def test_dispatcher_dispatch_delegates_to_batch(monkeypatch):
    spec = RouteSpec(metric="entropy", thresholds=(5.0,),
                     tier_names=("a", "b"), top_k=32)
    session = build(spec)
    calls = []
    orig = SkewRouteDispatcher.dispatch_batch

    def spy(self, *a, **kw):
        calls.append(kw)
        return orig(self, *a, **kw)

    monkeypatch.setattr(SkewRouteDispatcher, "dispatch_batch", spy)
    session.route_one(_desc_scores(1, 32)[0], n_valid=20)
    assert len(calls) == 1  # one entry point: no oracle/kernel divergence


# -- snapshot / restore -------------------------------------------------------

def _streaming_spec(k=32):
    return RouteSpec(
        metric="entropy", thresholds=(4.0,), tier_names=("small", "large"),
        top_k=k,
        calibration=CalibrationSpec(policy="streaming",
                                    target_shares=(0.7, 0.3),
                                    window=256, min_samples=32,
                                    tolerance=0.02, cooldown=32))


def test_snapshot_restore_bitexact_and_json():
    k = 32
    session = build(_streaming_spec(k))
    rng = np.random.default_rng(7)
    for i in range(6):  # drifting traffic -> hot-swaps fire
        flat = rng.uniform(0.4 + 0.1 * i, 1, (64, k)).astype(np.float32)
        session.route(np.sort(flat, axis=1)[:, ::-1].copy())
    assert session.stats.n_recalibrations > 0
    assert session.thresholds != (4.0,)  # thresholds actually moved

    snap = json.loads(json.dumps(session.snapshot()))  # full json trip
    twin = build(_streaming_spec(k)).restore(snap)

    assert twin.thresholds == session.thresholds          # bit-exact floats
    assert twin.calibrator.config.thresholds == \
        session.calibrator.config.thresholds
    np.testing.assert_array_equal(twin.calibrator.window.values(),
                                  session.calibrator.window.values())
    assert twin.calibrator.window.total_seen == \
        session.calibrator.window.total_seen
    assert twin.calibrator.events == session.calibrator.events
    assert twin.stats.n_requests == session.stats.n_requests
    assert twin.stats.tier_counts == session.stats.tier_counts
    assert twin.stats.total_cost == session.stats.total_cost

    # the twin continues IDENTICALLY: same tiers, same swap decisions
    probe = np.sort(rng.uniform(0.95, 1, (64, k)).astype(np.float32),
                    axis=1)[:, ::-1].copy()
    ra, rb = session.route(probe), twin.route(probe)
    assert np.array_equal(ra.tiers, rb.tiers)
    assert ra.recalibrated == rb.recalibrated
    assert twin.thresholds == session.thresholds


def test_from_snapshot_classmethod():
    session = build(_streaming_spec())
    session.route(_desc_scores(64, 32, seed=9))
    snap = session.snapshot()
    twin = SkewRouteSession.from_snapshot(snap)
    assert twin.spec == session.spec
    assert twin.stats.n_requests == 64


def test_window_state_rejects_capacity_mismatch():
    from repro.core.streaming_calibrate import SlidingWindow
    src = SlidingWindow(8)
    src.push(np.arange(20, dtype=np.float32))  # wrapped: 8 live, 20 seen
    state = src.state_dict()
    bigger = SlidingWindow(64)  # min(20, 64) > 8 -> would read junk
    with pytest.raises(ValueError, match="window state mismatch"):
        bigger.load_state_dict(state)
    same = SlidingWindow(8)
    same.load_state_dict(state)
    np.testing.assert_array_equal(same.values(), src.values())


def test_restore_rejects_foreign_spec():
    session = build(_streaming_spec())
    snap = session.snapshot()
    other = build(dataclasses.replace(_streaming_spec(), metric="area"))
    with pytest.raises(ValueError, match="different +RouteSpec"):
        other.restore(snap)


def test_snapshot_refuses_pending_payloads():
    spec = RouteSpec(metric="entropy", thresholds=(0.0,),
                     tier_names=("a", "b"), top_k=16, micro_batch=8)
    session = build(spec, runners={0: list, 1: list})
    session.submit(_desc_scores(3, 16))  # 3 < micro_batch: stays queued
    with pytest.raises(RuntimeError, match="flush"):
        session.snapshot()
    session.flush()
    json.dumps(session.snapshot())  # serializable once drained


# -- backends registry --------------------------------------------------------

def test_backend_registry_and_auto():
    assert {"oracle", "pallas", "fused", "auto"} <= set(available_backends())
    auto = make_backend("auto")
    assert isinstance(auto, backends_mod.AutoBackend)
    assert auto.crossover_batch == backends_mod.DEFAULT_CROSSOVER_BATCH
    # one kernel backend class, made under either name and reporting it
    for name in ("pallas", "fused"):
        kernel = make_backend(name)
        assert isinstance(kernel, PallasBackend) and kernel.name == name
        session = build(RouteSpec(backend=name, thresholds=(0.0,), top_k=8,
                                  tier_names=("a", "b")))
        assert session.telemetry()["backend"] == name
        assert session.snapshot()["policy"]["backend"] == name
    with pytest.raises(ValueError, match="unknown difficulty backend"):
        make_backend("quantum")
    with pytest.raises(ValueError, match="invalid backend name"):
        register_backend("auto", PallasBackend)

    class EchoBackend(OracleBackend):
        name = "echo"

    register_backend("echo", EchoBackend)
    try:
        assert "echo" in available_backends()
        spec = RouteSpec(backend="echo", thresholds=(0.0,), top_k=8,
                         tier_names=("a", "b"))
        assert build(spec).backend.name == "echo"
    finally:
        backends_mod._REGISTRY.pop("echo", None)


# -- deprecation shims --------------------------------------------------------

def test_old_constructors_warn_once():
    _deprecation.reset()
    cfg = RouterConfig(metric="entropy", thresholds=(5.0,))
    with pytest.warns(DeprecationWarning, match="repro.api.build"):
        d = SkewRouteDispatcher(cfg, ["a", "b"])
    with pytest.warns(DeprecationWarning, match="repro.api.build"):
        ServingPipeline(d, {0: list, 1: list})
    # second constructions are silent (warn-once)
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        d2 = SkewRouteDispatcher(cfg, ["a", "b"])
        ServingPipeline(d2, {0: list, 1: list})


def test_api_build_does_not_warn():
    _deprecation.reset()
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        build(RouteSpec(thresholds=(0.0,), tier_names=("a", "b")),
              runners={0: list, 1: list})
    _deprecation.reset()
