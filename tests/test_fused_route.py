"""End-to-end fused routing tests: `route_retrieved` vs the staged host
reference, the auto backend's batch-size crossover, and call-time
interpret resolution across snapshot/restore.

Parity bar matches the kernel suite (atol 1e-5) and deliberately covers
the awkward shapes: ragged per-query candidate counts, K that is not a
multiple of the kernel's 128 tile, and all four skew metrics. The
Figure-3 anchors are pushed through the WHOLE fused program via an
identity-passthrough scorer so the paper's printed area values survive
score -> top-k -> sigmoid -> skew intact.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

import repro.api.backends as backends_mod
from repro.api import (AutoBackend, OracleBackend, PallasBackend, RouteSpec,
                       build, make_backend)
from repro.core.router import (RouterConfig, route_retrieved,
                               route_retrieved_staged)
from tests.test_skew_fastpath import (FIG3_FLAT_BETA, FIG3_POWERLAW_ALPHA,
                                      fig3_flat, fig3_powerlaw)

ATOL = 1e-5

D_TRIPLE, D_QUERY, D_HIDDEN = 12, 8, 16


def _params(rng, dt=D_TRIPLE, dq=D_QUERY, h=D_HIDDEN):
    return {
        "w1_t": jnp.asarray(rng.normal(0, 0.3, (dt, h)).astype(np.float32)),
        "w1_q": jnp.asarray(rng.normal(0, 0.3, (dq, h)).astype(np.float32)),
        "b1": jnp.asarray(rng.normal(0, 0.1, (h,)).astype(np.float32)),
        "w2": jnp.asarray(rng.normal(0, 0.3, (h, 1)).astype(np.float32)),
        "b2": jnp.asarray(rng.normal(0, 0.1, (1,)).astype(np.float32)),
    }


def _batch(rng, b, n, dt=D_TRIPLE, dq=D_QUERY):
    feats = rng.normal(0, 1, (b, n, dt)).astype(np.float32)
    qemb = rng.normal(0, 1, (b, dq)).astype(np.float32)
    return jnp.asarray(feats), jnp.asarray(qemb)


def _assert_parity(fused, staged):
    np.testing.assert_array_equal(np.asarray(fused.tiers),
                                  np.asarray(staged.tiers))
    np.testing.assert_allclose(np.asarray(fused.metrics),
                               np.asarray(staged.metrics), atol=ATOL)
    np.testing.assert_allclose(np.asarray(fused.difficulty),
                               np.asarray(staged.difficulty), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(fused.n_valid),
                                  np.asarray(staged.n_valid))
    # retrieval output parity on the valid prefix only (pad cols free)
    f_idx, s_idx = np.asarray(fused.indices), np.asarray(staged.indices)
    f_p, s_p = np.asarray(fused.probs), np.asarray(staged.probs)
    for i, nv in enumerate(np.asarray(fused.n_valid)):
        np.testing.assert_array_equal(f_idx[i, :nv], s_idx[i, :nv])
        np.testing.assert_allclose(f_p[i, :nv], s_p[i, :nv], atol=ATOL)


# -- fused vs staged parity ---------------------------------------------------

@pytest.mark.parametrize("metric", ["area", "cumulative", "entropy", "gini"])
@pytest.mark.parametrize("use_kernels", [True, False])
def test_fused_matches_staged_all_metrics(metric, use_kernels):
    """One program == four host stages, for every skew metric, with the
    Pallas kernels (interpret) AND the XLA refs traced into the chain."""
    rng = np.random.default_rng(hash(metric) % 2**31)
    feats, qemb = _batch(rng, b=6, n=64)
    params = _params(rng)
    config = RouterConfig(metric=metric, thresholds=(0.3, 3.0), top_k=32)
    fused = route_retrieved(feats, qemb, params, config,
                            interpret=True, use_kernels=use_kernels)
    staged = route_retrieved_staged(feats, qemb, params, config)
    _assert_parity(fused, staged)


def test_fused_matches_staged_ragged_and_odd_k():
    """Ragged n_cand (some rows shorter than K) and K=37 — not a multiple
    of the triple_score kernel's 128 tile, N not a multiple either."""
    rng = np.random.default_rng(7)
    feats, qemb = _batch(rng, b=8, n=50)
    params = _params(rng)
    n_cand = np.array([50, 3, 37, 12, 50, 1, 49, 25], np.int32)
    config = RouterConfig(metric="gini", thresholds=(0.5,), top_k=37)
    fused = route_retrieved(feats, qemb, params, config, n_cand=n_cand,
                            interpret=True, use_kernels=True)
    staged = route_retrieved_staged(feats, qemb, params, config,
                                    n_cand=n_cand)
    _assert_parity(fused, staged)
    assert np.asarray(fused.n_valid).tolist() == \
        np.minimum(n_cand, 37).tolist()


def test_fused_kernels_vs_oracle_chain():
    """The kernel-built program and the XLA-built program are the same
    function (this is what makes the crossover a pure perf policy)."""
    rng = np.random.default_rng(11)
    feats, qemb = _batch(rng, b=5, n=40)
    params = _params(rng)
    config = RouterConfig(metric="entropy", thresholds=(4.0,), top_k=16)
    a = route_retrieved(feats, qemb, params, config,
                        interpret=True, use_kernels=True)
    b = route_retrieved(feats, qemb, params, config,
                        interpret=True, use_kernels=False)
    np.testing.assert_array_equal(np.asarray(a.tiers), np.asarray(b.tiers))
    np.testing.assert_allclose(np.asarray(a.metrics),
                               np.asarray(b.metrics), atol=ATOL)
    np.testing.assert_array_equal(np.asarray(a.indices),
                                  np.asarray(b.indices))


# -- Figure-3 anchors through the whole program -------------------------------

def _passthrough_params(dt):
    """A scorer whose output IS feature 0: relu(f0) - relu(-f0) = f0.
    Lets known probability vectors ride through score -> top-k ->
    sigmoid untouched (modulo float32 logit/sigmoid round-trip)."""
    w1_t = np.zeros((dt, 2), np.float32)
    w1_t[0, 0], w1_t[0, 1] = 1.0, -1.0
    return {
        "w1_t": jnp.asarray(w1_t),
        "w1_q": jnp.zeros((D_QUERY, 2), jnp.float32),
        "b1": jnp.zeros((2,), jnp.float32),
        "w2": jnp.asarray(np.array([[1.0], [-1.0]], np.float32)),
        "b2": jnp.zeros((1,), jnp.float32),
    }


@pytest.mark.parametrize("use_kernels", [True, False])
def test_fig3_anchors_through_fused_program(use_kernels):
    """Paper Figure-3 anchor vectors fed as logits: the fused program's
    area metric must land on the printed values (1.07 power-law easy,
    65.65 flat hard) after the full score->top-k->sigmoid->skew chain."""
    k = 100
    probs = np.stack([fig3_powerlaw(k), fig3_flat(k)])  # [2, 100] in (0,1]
    p = np.clip(probs, 1e-7, 1.0 - 1e-6)
    logits = np.log(p) - np.log1p(-p)                   # sigmoid^-1
    feats = np.zeros((2, k, D_TRIPLE), np.float32)
    feats[:, :, 0] = logits
    # shuffle candidate order: top-k must restore the descending vectors
    rng = np.random.default_rng(0)
    perm = rng.permutation(k)
    feats = feats[:, perm, :]
    qemb = np.zeros((2, D_QUERY), np.float32)
    config = RouterConfig(metric="area", thresholds=(10.0,), top_k=k)
    out = route_retrieved(jnp.asarray(feats), jnp.asarray(qemb),
                          _passthrough_params(D_TRIPLE), config,
                          interpret=True, use_kernels=use_kernels)
    area = np.asarray(out.metrics)[:, 0]
    np.testing.assert_allclose(area, [1.07, 65.65], atol=5e-3)
    # and the tiers split exactly as the paper reads Figure 3:
    # peaked scores -> easy (small model), flat scores -> hard
    assert np.asarray(out.tiers).tolist() == [0, 1]
    np.testing.assert_allclose(np.sort(np.asarray(out.probs)[0])[::-1],
                               p[0], atol=1e-4)


# -- auto backend crossover ---------------------------------------------------

def test_auto_crossover_pick_boundaries():
    auto = AutoBackend(crossover_batch=4)
    assert auto.pick(1) is auto.oracle
    assert auto.pick(3) is auto.oracle
    assert auto.pick(4) is auto.fused
    assert auto.pick(4096) is auto.fused
    assert isinstance(auto.oracle, OracleBackend)
    assert isinstance(auto.fused, PallasBackend)
    assert (auto.oracle.name, auto.fused.name) == ("oracle", "fused")


def test_auto_routes_by_leading_dim():
    """route_batch/route_retrieved agree with an explicit pick() — and
    both sides of the crossover give the SAME answers."""
    rng = np.random.default_rng(3)
    scores = np.sort(rng.uniform(0.01, 1, (8, 20)).astype(np.float32),
                     axis=1)[:, ::-1].copy()
    config = RouterConfig(metric="gini", thresholds=(0.5,), top_k=20)
    below = AutoBackend(crossover_batch=100).route_batch(scores, config)
    above = AutoBackend(crossover_batch=2).route_batch(scores, config)
    np.testing.assert_array_equal(np.asarray(below.tiers),
                                  np.asarray(above.tiers))
    np.testing.assert_allclose(np.asarray(below.metrics),
                               np.asarray(above.metrics), atol=ATOL)


def test_auto_crossover_validation():
    with pytest.raises(ValueError, match="crossover_batch"):
        AutoBackend(crossover_batch=0)
    with pytest.raises(ValueError, match="crossover_batch"):
        RouteSpec(metric="gini", thresholds=(0.5,), tier_names=("a", "b"),
                  crossover_batch=0)


def test_crossover_rides_the_spec():
    spec = RouteSpec(metric="gini", thresholds=(0.5,), tier_names=("a", "b"),
                     backend="auto", crossover_batch=7)
    spec2 = RouteSpec.from_json(spec.to_json())
    assert spec2.crossover_batch == 7
    session = build(spec2)
    assert isinstance(session.backend, AutoBackend)
    assert session.backend.crossover_batch == 7
    # old payloads (no field) load with the default
    payload = json.loads(spec.to_json())
    del payload["crossover_batch"]
    old = RouteSpec.from_dict(payload)
    assert old.crossover_batch == backends_mod.DEFAULT_CROSSOVER_BATCH


# -- call-time interpret resolution across snapshot/restore -------------------

def test_restore_re_resolves_interpret(monkeypatch):
    """A snapshot taken on one host class (say TPU, interpret False) and
    restored on another (CPU) must NOT replay the donor's interpret mode:
    the spec/snapshot carry no interpret bit, and the restored backend
    re-resolves `default_interpret()` at every call."""
    spec = RouteSpec(metric="gini", thresholds=(0.5,), tier_names=("a", "b"),
                     backend="auto", top_k=16)
    session = build(spec)
    session.route(np.sort(
        np.random.default_rng(0).uniform(0.01, 1, (4, 16)).astype(
            np.float32), axis=1)[:, ::-1].copy())
    snap = json.loads(json.dumps(session.snapshot()))  # wire round-trip
    assert "interpret" not in json.dumps(snap)

    restored = build(RouteSpec.from_json(spec.to_json()))
    restored.restore(snap)
    assert restored.backend.interpret is None  # never baked in

    # flip what the "local device" claims to be: the restored backend
    # must follow, proving resolution happens at call time
    monkeypatch.setattr(backends_mod, "default_interpret", lambda: True)
    assert restored.backend.effective_interpret() is True
    monkeypatch.setattr(backends_mod, "default_interpret", lambda: False)
    assert restored.backend.effective_interpret() is False

    # an explicit override still wins over the device default
    assert make_backend("fused", interpret=True).effective_interpret() is True
