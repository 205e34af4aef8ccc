"""Fused fast-path tests: Pallas skew_metrics vs the XLA oracle.

Property-based parity (random batches, K incl. non-multiples of 128,
ragged masks, constant and power-law score vectors) within float32
reordering of sums of up to K terms (``SUM_TOL``), golden
values pinning the paper's Figure-3 anchors, metric range invariants, and
the batched routing entry (`route_all_metrics`) against the per-request
oracle path. Everything runs in interpret mode (CPU container).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import skewness as sk
from repro.core.router import (RouterConfig, difficulty_from_metrics, route,
                               route_all_metrics)
from repro.kernels.skew_metrics import ops
from tests._hypothesis_compat import given, st

ATOL = 1e-5  # a constant row's area: zero on both sides up to rounding
#: Kernel against oracle, and the batched entry's difficulty against the
#: per-request oracle: each metric is a float32 sum of at most K = 100 terms of size up to 1 (or
#: up to the sum itself: area reaches ~100), taken by the kernel in
#: another order than by the oracle. Such sums may differ by a few ulps
#: of the result; K ulps (K * eps32 = 1.2e-5), absolute and relative to
#: the value, bounds that (area near 52.5 drew 1.5e-5, 4 ulps), while a
#: wrong metric or scores shifted by one rank move a difficulty by far
#: more (0.1 and up).
SUM_TOL = 100 * float(np.finfo(np.float32).eps)

# Figure-3 anchor generators: exponents solved so the K=100 area metric
# lands exactly on the paper's printed values (1.07 power-law, 65.65 flat).
FIG3_POWERLAW_ALPHA = 4.195657
FIG3_FLAT_BETA = 0.430239


def fig3_powerlaw(k=100):
    return (1.0 / np.arange(1, k + 1) ** FIG3_POWERLAW_ALPHA).astype(
        np.float32)


def fig3_flat(k=100):
    return ((1.0 - np.arange(k) / k) ** FIG3_FLAT_BETA).astype(np.float32)


def desc_scores(rng, b, k, lo=0.01, hi=1.0):
    return np.sort(rng.uniform(lo, hi, (b, k)).astype(np.float32),
                   axis=1)[:, ::-1].copy()


def kernel_vs_oracle(scores, n_valid=None, p_cdf=0.95):
    s = jnp.asarray(scores)
    nv = None if n_valid is None else jnp.asarray(n_valid)
    out = ops.skew_metrics(s, p_cdf=p_cdf, n_valid=nv, interpret=True)
    mask = None if n_valid is None else ops.mask_from_n_valid(
        nv, scores.shape[1])
    ref = ops.skew_metrics_ref(s, p_cdf=p_cdf, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=SUM_TOL, atol=SUM_TOL)
    return np.asarray(out)


# -- property parity ----------------------------------------------------------

@given(st.integers(1, 24), st.integers(5, 200), st.integers(0, 10_000))
def test_parity_random_batches(rows, k, seed):
    """Dense descending batches, K deliberately spanning non-multiples of
    128 (the kernel's lane padding)."""
    rng = np.random.default_rng(seed)
    kernel_vs_oracle(desc_scores(rng, rows, k))


@given(st.integers(2, 16), st.integers(10, 180), st.integers(0, 10_000))
def test_parity_ragged_masks(rows, k, seed):
    """Per-row n_valid (kernel) == prefix mask (oracle)."""
    rng = np.random.default_rng(seed)
    scores = desc_scores(rng, rows, k, lo=-0.5, hi=1.0)  # logits: negatives
    n_valid = rng.integers(1, k + 1, rows).astype(np.int32)
    kernel_vs_oracle(scores, n_valid=n_valid)


@given(st.floats(-2.0, 2.0), st.integers(2, 128))
def test_parity_constant_vectors(value, k):
    """Constant scores: area 0, uniform probs — both paths must agree on
    the degenerate normalizations."""
    scores = np.full((3, k), np.float32(value))
    out = kernel_vs_oracle(scores)
    np.testing.assert_allclose(out[:, 0], 0.0, atol=ATOL)          # area
    # uniform distribution => max entropy, once the row total is far above
    # the 1e-12 guard both normalizations add (a total near it is not a
    # distribution: float32(2.2e-16) * 2 gives entropy 0.005, not 1)
    if np.float32(value) * k > 1e-6:
        np.testing.assert_allclose(out[:, 2], np.log2(k), atol=1e-4)
        np.testing.assert_allclose(out[:, 3], 0.0, atol=1e-4)      # gini


@given(st.floats(0.5, 5.0), st.integers(20, 160), st.integers(0, 100))
def test_parity_powerlaw_vectors(alpha, k, seed):
    rng = np.random.default_rng(seed)
    base = 1.0 / np.arange(1, k + 1) ** alpha
    batch = np.stack([base * s for s in rng.uniform(0.5, 2.0, 4)]).astype(
        np.float32)
    kernel_vs_oracle(batch)


@given(st.sampled_from([0.5, 0.8, 0.9, 0.95, 0.99]), st.integers(0, 1000))
def test_parity_cumulative_p_sweep(p_cdf, seed):
    """cumulative-k is the integer-valued metric (paper Fig 9 sweeps P);
    parity must hold exactly across P, not just at the 0.95 default."""
    rng = np.random.default_rng(seed)
    kernel_vs_oracle(desc_scores(rng, 8, 100), p_cdf=p_cdf)


def test_parity_lane_boundary_shapes():
    """K exactly at / around the 128-lane tile edge."""
    rng = np.random.default_rng(7)
    for k in [127, 128, 129, 255, 256]:
        kernel_vs_oracle(desc_scores(rng, 5, k))


# -- golden values (paper Figure 3) -------------------------------------------

def test_figure3_area_anchors():
    """Paper Fig 3c/3d: area 1.07 (power-law example) vs 65.65 (flat) at
    K=100 — pinned on both the oracle and the fused kernel."""
    batch = jnp.asarray(np.stack([fig3_powerlaw(), fig3_flat()]))
    oracle_area = np.asarray(sk.area_metric(batch))
    kernel_area = np.asarray(ops.skew_metrics(batch, interpret=True))[:, 0]
    for area in (oracle_area, kernel_area):
        np.testing.assert_allclose(area, [1.07, 65.65], atol=5e-3)


def test_figure3_direction_on_all_metrics():
    """The same two Figure-3 vectors must separate on every difficulty
    metric (flat = hard > power-law = easy)."""
    batch = jnp.asarray(np.stack([fig3_powerlaw(), fig3_flat()]))
    metrics = np.asarray(ops.skew_metrics(batch, interpret=True))
    for name in ops.METRIC_COLUMNS:
        diff = np.asarray(difficulty_from_metrics(jnp.asarray(metrics), name))
        assert diff[1] > diff[0], name


# -- range invariants ---------------------------------------------------------

@given(st.integers(2, 150), st.integers(0, 10_000))
def test_metric_ranges_kernel(k, seed):
    """entropy in [0, log2 K], gini in [0, 1 - 1/K], cumulative in [1, K],
    area in [0, K] — on the KERNEL output (the oracle variant lives in
    test_skewness.py)."""
    rng = np.random.default_rng(seed)
    out = np.asarray(ops.skew_metrics(jnp.asarray(desc_scores(rng, 4, k)),
                                      interpret=True))
    tol = 1e-4
    assert (out[:, 0] >= -tol).all() and (out[:, 0] <= k + tol).all()
    assert (out[:, 1] >= 1).all() and (out[:, 1] <= k).all()
    assert (out[:, 2] >= -tol).all()
    assert (out[:, 2] <= np.log2(k) + tol).all()
    assert (out[:, 3] >= -tol).all()
    assert (out[:, 3] <= 1.0 - 1.0 / k + tol).all()


def test_gini_upper_bound_attained():
    onehot = np.zeros((1, 64), np.float32)
    onehot[0, 0] = 1.0
    out = np.asarray(ops.skew_metrics(jnp.asarray(onehot), interpret=True))
    np.testing.assert_allclose(out[0, 3], 1.0 - 1.0 / 64, atol=1e-6)


# -- batched routing entry ----------------------------------------------------

@given(st.sampled_from(["area", "cumulative", "entropy", "gini"]),
       st.integers(0, 1000))
def test_route_all_metrics_matches_oracle_route(metric, seed):
    rng = np.random.default_rng(seed)
    scores = desc_scores(rng, 40, 100)
    diff = sk.difficulty(jnp.asarray(scores), metric=metric)
    thetas = tuple(np.quantile(np.asarray(diff), [0.5, 0.8]))
    cfg = RouterConfig(metric=metric, thresholds=thetas)
    oracle_tiers = np.asarray(route(jnp.asarray(scores), cfg))
    res = route_all_metrics(jnp.asarray(scores), cfg, interpret=True)
    np.testing.assert_array_equal(np.asarray(res.tiers), oracle_tiers)
    np.testing.assert_allclose(np.asarray(res.difficulty), np.asarray(diff),
                               rtol=SUM_TOL, atol=SUM_TOL)
    assert res.metrics.shape == (40, 4)


def test_difficulty_from_metrics_rejects_unknown():
    with pytest.raises(ValueError, match="unknown metric"):
        difficulty_from_metrics(jnp.zeros((2, 4)), "nope")


def test_dispatcher_batch_matches_oracle_and_buckets():
    """dispatch_batch (fused, bucket-padded) == oracle route decisions,
    independent of batch size bucketing."""
    from repro.serving.router_service import SkewRouteDispatcher
    rng = np.random.default_rng(3)
    scores = desc_scores(rng, 50, 100)
    diff = sk.difficulty(jnp.asarray(scores), metric="gini")
    cfg = RouterConfig(metric="gini",
                       thresholds=(float(np.quantile(np.asarray(diff), 0.7)),))
    oracle_tiers = np.asarray(route(jnp.asarray(scores), cfg))
    d = SkewRouteDispatcher(cfg, ["small", "large"])
    np.testing.assert_array_equal(d.dispatch_batch(scores), oracle_tiers)
    # odd sub-batch sizes exercise different pad buckets
    got = np.concatenate([d.dispatch_batch(scores[:7]),
                          d.dispatch_batch(scores[7:19]),
                          d.dispatch_batch(scores[19:])])
    np.testing.assert_array_equal(got, oracle_tiers)
    assert d.stats.n_requests == 100
    # per-request path agrees with the batch path
    rec = d.dispatch(scores[0])
    assert rec.tier == int(oracle_tiers[0])


def test_n_valid_zero_clamps_to_one():
    """Pinned edge semantics: n_valid=0 is clamped to 1 (one degenerate
    entry, no NaNs) — it does NOT match the oracle's all-false mask,
    which reports cumulative_k = 0 (documented in kernel.py)."""
    scores = np.zeros((2, 64), np.float32)
    out = np.asarray(ops.skew_metrics(jnp.asarray(scores),
                                      n_valid=jnp.asarray([0, 0]),
                                      interpret=True))
    assert np.isfinite(out).all()
    one = np.asarray(ops.skew_metrics(jnp.asarray(scores),
                                      n_valid=jnp.asarray([1, 1]),
                                      interpret=True))
    np.testing.assert_array_equal(out, one)


def test_dispatcher_ragged_n_valid():
    from repro.serving.router_service import SkewRouteDispatcher
    rng = np.random.default_rng(4)
    k = 100
    scores = desc_scores(rng, 16, k)
    n_valid = rng.integers(5, k + 1, 16).astype(np.int32)
    cfg = RouterConfig(metric="entropy", thresholds=(5.0,))
    d = SkewRouteDispatcher(cfg, ["small", "large"])
    tiers = d.dispatch_batch(scores, n_valid=n_valid)
    mask = np.arange(k)[None, :] < n_valid[:, None]
    expected = np.asarray(route(jnp.asarray(scores), cfg,
                                mask=jnp.asarray(mask)))
    np.testing.assert_array_equal(tiers, expected)
