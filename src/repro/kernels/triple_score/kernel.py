"""Fused SubgraphRAG triple-scorer Pallas kernel.

The retrieval hot path (paper §2: scorer R over candidate triples): for a
query batch, millions of candidate triples each get a relevance score from
a 2-layer MLP over [triple_features ++ query_embedding]. Because the query
part is shared across all triples of a query, the kernel splits the first
layer as

    h = relu(T @ W1_t  +  (q @ W1_q + b1))     score = h @ w2 + b2

and keeps all weights + the per-query bias VMEM-resident while streaming
128-triple tiles from HBM — one pass, no [N, hidden] intermediate in HBM.
The GPU baseline (SubgraphRAG) runs this as separate GEMM + bias + GEMM
launches with the hidden activations round-tripping through HBM.

Grid: (queries, triple_tiles); both parallel.
Layout (a TPU block's last two dims must be divisible by (8, 128) or equal
the array's): the per-query bias is a [Q, 1, H] array read as a squeezed
(None, 1, H) block; scores are written as one lane-dense [1, tile] row of
a [Q, 1, N] array; ``w2`` enters as a [1, H] row and ``b2`` as an SMEM
scalar. The second layer is an NT dot of the ``w2`` row against ``h``,
which gives the [1, tile] score row directly.
VMEM: W1_t [Dt, H] + tile [128, Dt] + h [128, H] + two [1, H] rows — for
Dt=1156, H=1024 (paper-scale) ≈ 5 MiB, within budget.

Precision: every dot here and in the XLA reference (`ref.py`) runs at
``Precision.HIGHEST``. A TPU f32 dot at default precision is a single bf16
pass, and two programs that round differently can swap near-tied
candidates in the top-k; full f32 on both sides keeps them within f32
rounding of each other.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE = 128
PRECISION = jax.lax.Precision.HIGHEST

_NT = (((1,), (1,)), ((), ()))  # contract the last dim of both operands


def _score_kernel(t_ref, qb_ref, w1_ref, w2_ref, b2_ref, o_ref):
    t = t_ref[...].astype(jnp.float32)            # [tile, Dt]
    w1 = w1_ref[...].astype(jnp.float32)          # [Dt, H]
    qb = qb_ref[...]                              # [1, H] query bias
    h = jax.lax.dot(t, w1, precision=PRECISION,
                    preferred_element_type=jnp.float32) + qb
    h = jnp.maximum(h, 0.0)
    w2 = w2_ref[...].astype(jnp.float32)          # [1, H]
    score = jax.lax.dot_general(w2, h, _NT, precision=PRECISION,
                                preferred_element_type=jnp.float32)
    o_ref[...] = score + b2_ref[0]                # [1, tile]


def _pad_to(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _score_call(triple_feats, t_spec: pl.BlockSpec, query_emb, w1_t, w1_q,
                b1, w2, b2, n_out: int, tile: int,
                interpret: bool) -> jax.Array:
    """The pallas_call both entry points share; they differ only in how a
    (query, tile) grid cell finds its triple tile (``t_spec``).
    Returns [Q, n_out] scores."""
    q_count = query_emb.shape[0]
    dt, h_dim = w1_t.shape
    # per-query first-layer bias, computed once (tiny GEMM): [Q, 1, H]
    q_bias = (jnp.dot(query_emb.astype(jnp.float32),
                      w1_q.astype(jnp.float32), precision=PRECISION)
              + b1.astype(jnp.float32))[:, None, :]
    out = pl.pallas_call(
        _score_kernel,
        grid=(q_count, n_out // tile),
        in_specs=[
            t_spec,
            pl.BlockSpec((None, 1, h_dim), lambda iq, it: (iq, 0, 0)),
            pl.BlockSpec((dt, h_dim), lambda iq, it: (0, 0)),
            pl.BlockSpec((1, h_dim), lambda iq, it: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, 1, tile), lambda iq, it: (iq, 0, it)),
        out_shape=jax.ShapeDtypeStruct((q_count, 1, n_out), jnp.float32),
        interpret=interpret,
    )(triple_feats, q_bias, w1_t, w2.reshape(1, h_dim),
      b2.astype(jnp.float32))
    return out[:, 0, :]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def triple_score(triple_feats: jax.Array, query_emb: jax.Array,
                 w1_t: jax.Array, w1_q: jax.Array, b1: jax.Array,
                 w2: jax.Array, b2: jax.Array,
                 tile: int = DEFAULT_TILE, interpret: bool = False) -> jax.Array:
    """Score N triples for Q queries.

    triple_feats: [N, Dt]; query_emb: [Q, Dq]; w1_t: [Dt, H]; w1_q: [Dq, H];
    b1: [H]; w2: [H, 1]; b2: [1]  ->  scores [Q, N].
    """
    n, dt = triple_feats.shape
    if n % tile:
        raise ValueError(f"N={n} not divisible by tile={tile}")
    t_spec = pl.BlockSpec((tile, dt), lambda iq, it: (it, 0))
    return _score_call(triple_feats, t_spec, query_emb, w1_t, w1_q, b1, w2,
                       b2, n, tile, interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def triple_score_batched(triple_feats: jax.Array, query_emb: jax.Array,
                         w1_t: jax.Array, w1_q: jax.Array, b1: jax.Array,
                         w2: jax.Array, b2: jax.Array,
                         tile: int = DEFAULT_TILE,
                         interpret: bool = False) -> jax.Array:
    """Per-query candidate sets: each query scores only ITS OWN triples.

    triple_feats: [B, N, Dt]; query_emb: [B, Dq] -> scores [B, N].

    Same kernel body as :func:`triple_score` — the triple block is a
    squeezed (None, tile, Dt) window of query ``iq``'s own [Npad, Dt]
    slice, so weights and the per-query bias stay VMEM-resident exactly as
    in the shared-candidate variant. N is padded up to the tile size
    internally; padded rows are zero-feature triples whose scores are
    sliced off before returning (callers masking ragged candidate sets
    still pass their own ``n_cand`` downstream — see
    `repro.core.router.route_retrieved`).
    """
    _, n, dt = triple_feats.shape
    npad = _pad_to(n, tile)
    feats = jnp.pad(triple_feats, ((0, 0), (0, npad - n), (0, 0)))
    t_spec = pl.BlockSpec((None, tile, dt), lambda iq, it: (iq, it, 0))
    out = _score_call(feats, t_spec, query_emb, w1_t, w1_q, b1, w2, b2,
                      npad, tile, interpret)
    return out[:, :n]
