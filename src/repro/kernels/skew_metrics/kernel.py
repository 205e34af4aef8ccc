"""Fused skewness-metric Pallas kernel — the SkewRoute router fast path.

Every request pays this op (paper Algorithm 1): given the top-K retrieval
scores (descending-sorted, as emitted by top-k), compute all four
difficulty metrics in ONE pass over a [rows, K] tile:

  col 0  area          sum(minmax-normalized)
  col 1  cumulative-k  #contexts to reach CDF >= P
  col 2  entropy       -sum p log2 p
  col 3  gini          (K+1 - 2 sum (K-i+1) s'_i / sum) / K

The descending order is exploited twice: the CDF needs no sort, and the
paper's ascending-rank Gini weight (K - i + 1) collapses to (column + 1)
for descending data — `repro.core.skewness` (the XLA oracle) sorts twice
instead.

Ragged retrieval is first-class: an optional per-row ``n_valid`` vector
(matching the oracle's prefix-``mask`` support) rides along as a
[rows, 1] int32 block; every reduction masks columns >= n_valid and the
Gini/cumulative normalizers use the per-row count. All four metrics are
always emitted, so the router's metric choice is a column select — never
a recompile.

Grid: row tiles; one [rows_tile, Kpad] VMEM block, four VPU reductions
and one MXU pass, one lane-dense [rows_tile, 128] store whose first four
columns are the metrics (sliced outside the kernel). K=100 pads to 128
lanes with mask-aware reductions. Every intermediate stays 2-D
(``keepdims=True``): TPU vectors are (sublane, lane) tiles.

The CDF is a running sum, which Mosaic has no lowering for; it is the
matmul ``prob @ U`` with U the [Kpad, Kpad] upper-triangular ones, at
``Precision.HIGHEST``. A default-precision f32 dot on a TPU is one bf16
pass, whose rounding can move cumulative-k by a whole context.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_ROW_TILE = 8
_EPS = 1e-12
_LANES = 128

METRIC_COLUMNS = ("area", "cumulative", "entropy", "gini")


def _skew_kernel(s_ref, nv_ref, o_ref, *, p_cdf: float):
    s = s_ref[...].astype(jnp.float32)                     # [rows, Kpad]
    rows, kpad = s.shape
    nv = nv_ref[...]                                       # [rows, 1] int32
    nvf = nv.astype(jnp.float32)                           # [rows, 1]
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, kpad), 1)
    valid = col < nv

    # min-max normalize (masked)
    s_hi = jnp.max(jnp.where(valid, s, -jnp.inf), axis=1, keepdims=True)
    s_lo = jnp.min(jnp.where(valid, s, jnp.inf), axis=1, keepdims=True)
    norm = jnp.where(valid, (s - s_lo) / (s_hi - s_lo + _EPS), 0.0)
    area = jnp.sum(norm, axis=1, keepdims=True)

    # probability normalization (shift only if negatives, like the oracle)
    shifted = jnp.where(valid, s - jnp.minimum(s_lo, 0.0), 0.0)
    total = jnp.sum(shifted, axis=1, keepdims=True)
    prob = shifted / (total + _EPS)

    # cumulative-k: scores arrive descending, so CDF = running sum
    upper = (jax.lax.broadcasted_iota(jnp.int32, (kpad, kpad), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (kpad, kpad), 1))
    cdf = jax.lax.dot(prob, upper.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    below = jnp.where(valid, (cdf < p_cdf - _EPS).astype(jnp.float32), 0.0)
    cum_k = jnp.minimum(jnp.sum(below, axis=1, keepdims=True) + 1.0, nvf)

    # entropy (bits) — jnp.log2 to match the oracle's formulation exactly
    plogp = jnp.where(prob > _EPS, prob * jnp.log2(prob + _EPS), 0.0)
    entropy = -jnp.sum(plogp, axis=1, keepdims=True)

    # gini: paper weight (n - asc_rank + 1) over ascending-sorted data is
    # just (col + 1) for descending-sorted data
    weight = jnp.where(valid, (col + 1).astype(jnp.float32), 0.0)
    weighted = jnp.sum(weight * shifted, axis=1, keepdims=True)
    n1 = jnp.maximum(nvf, 1.0)
    gini = (n1 + 1.0 - 2.0 * weighted / (total + _EPS)) / n1
    gini = jnp.clip(gini, 0.0, 1.0)

    # one lane-dense row per query: column c holds METRIC_COLUMNS[c]
    out_col = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    out = jnp.zeros((rows, _LANES), jnp.float32)
    for c, metric in enumerate((area, cum_k, entropy, gini)):
        out = jnp.where(out_col == c, metric, out)
    o_ref[...] = out


@functools.partial(jax.jit,
                   static_argnames=("p_cdf", "row_tile", "interpret"))
def skew_metrics(scores_desc: jax.Array,
                 n_valid: jax.Array | None = None,
                 p_cdf: float = 0.95,
                 row_tile: int = DEFAULT_ROW_TILE,
                 interpret: bool = False) -> jax.Array:
    """[B, K] descending-sorted -> [B, 4] (area, k@P, H, gini).

    ``n_valid``: optional [B] int32 count of valid leading entries per row
    (ragged retrieval); defaults to K everywhere. Clamped to [1, K]: an
    empty retrieval (0) is treated as one degenerate entry — the oracle's
    all-false mask instead reports cumulative_k = 0, so route zero-hit
    requests before they reach the kernel.
    """
    b, k = scores_desc.shape
    kpad = -(-k // 128) * 128
    bpad = -(-b // row_tile) * row_tile
    s = jnp.pad(scores_desc, ((0, bpad - b), (0, kpad - k)))
    if n_valid is None:
        nv = jnp.full((b,), k, jnp.int32)
    else:
        nv = jnp.clip(jnp.asarray(n_valid, jnp.int32), 1, k)
    nv = jnp.pad(nv, (0, bpad - b), constant_values=1)[:, None]
    out = pl.pallas_call(
        functools.partial(_skew_kernel, p_cdf=p_cdf),
        grid=(bpad // row_tile,),
        in_specs=[pl.BlockSpec((row_tile, kpad), lambda i: (i, 0)),
                  pl.BlockSpec((row_tile, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((row_tile, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bpad, _LANES), jnp.float32),
        interpret=interpret,
    )(s, nv)
    return out[:b, :len(METRIC_COLUMNS)]
