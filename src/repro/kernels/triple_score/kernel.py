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

Grid: (queries, triple_tiles); both parallel. The grid is ragged: each
query's count of tiles to score is scalar-prefetched data (SMEM), so one
compile serves every mix of counts. A step past its query's count runs no
matmul and writes 0.0; its triple block's index map holds the block that
the step before it read, so the pipeline issues no copy for it.
Layout (a TPU block's last two dims must be divisible by (8, 128) or equal
the array's): the per-query bias is a [Q, 1, H] array read as a squeezed
(None, 1, H) block; scores are written as one lane-dense [1, tile] row of
a [Q, 1, N] array; ``w2`` enters as a [1, H] row and ``b2`` as an SMEM
scalar. The second layer is an NT dot of the ``w2`` row against ``h``,
which gives the [1, tile] score row directly.
VMEM: W1_t [Dt, H] + tile [128, Dt] + h [128, H] + two [1, H] rows, each
block double-buffered — for Dt=1156, H=1024 (paper-scale) ≈ 11 MiB, within
the 16 MiB default; at gte-large width (Dt=3086) ≈ 30 MiB, so the call
raises its scoped-VMEM limit (`_scoped_vmem`).

Precision: every dot here and in the XLA reference (`ref.py`) runs at
``Precision.HIGHEST``. A TPU f32 dot at default precision is a single bf16
pass, and two programs that round differently can swap near-tied
candidates in the top-k; full f32 on both sides keeps them within f32
rounding of each other.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE = 128
#: the score a skipped tile's slots hold
FILL = 0.0
PRECISION = jax.lax.Precision.HIGHEST
#: Mosaic's default scoped-VMEM limit on a TPU v5e; a v5e core has 128 MiB.
DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024
#: the most scoped VMEM a call may ask for, leaving the rest to the compiler
MAX_SCOPED_VMEM = 100 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))  # contract the last dim of both operands


def _score_kernel(last_ref, t_ref, qb_ref, w1_ref, w2_ref, b2_ref, o_ref):
    iq, it = pl.program_id(0), pl.program_id(1)
    scored = iq * pl.num_programs(1) + it <= last_ref[iq]

    @pl.when(scored)
    def _():
        t = t_ref[...].astype(jnp.float32)        # [tile, Dt]
        w1 = w1_ref[...].astype(jnp.float32)      # [Dt, H]
        qb = qb_ref[...]                          # [1, H] query bias
        h = jax.lax.dot(t, w1, precision=PRECISION,
                        preferred_element_type=jnp.float32) + qb
        h = jnp.maximum(h, 0.0)
        w2 = w2_ref[...].astype(jnp.float32)      # [1, H]
        score = jax.lax.dot_general(w2, h, _NT, precision=PRECISION,
                                    preferred_element_type=jnp.float32)
        o_ref[...] = score + b2_ref[0]            # [1, tile]

    @pl.when(jnp.logical_not(scored))
    def _():
        o_ref[...] = jnp.full(o_ref.shape, FILL, o_ref.dtype)


def _last_blocks(row_tiles: jax.Array, n_tiles: int) -> jax.Array:
    """[Q] tiles to score per query -> [Q] int32: the last triple block
    each query's grid steps read, as ``query * n_tiles + tile``. A query
    with tiles reads its own, up to its last; one with none holds the
    block read before it (-1, read as block 0, if none was), so its steps
    all skip and fetch nothing."""
    rows = jnp.arange(row_tiles.shape[0], dtype=jnp.int32)
    own = jnp.where(row_tiles > 0, rows * n_tiles + row_tiles - 1, -1)
    return jax.lax.cummax(own, axis=0)


def _pad_to(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _scoped_vmem(tile: int, dt: int, h_dim: int):
    """Compiler parameters that raise the scoped-VMEM limit where the
    kernel's blocks need more than the default: every block twice (the
    pipeline's two buffers, the resident ``W1_t`` included) and the
    ``[tile, H]`` hidden activations, with a quarter for Mosaic's own
    scratch. At gte-large width (Dt=3086, H=1024) ``W1_t`` alone is 12 MiB,
    and the default 16 MiB does not hold two of it. None: the default
    holds."""
    blocks = (tile * dt + dt * h_dim + 2 * h_dim + tile) * 4
    need = int(1.25 * (2 * blocks + 2 * tile * h_dim * 4))
    if need <= DEFAULT_SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(need, MAX_SCOPED_VMEM))


def _score_call(triple_feats, t_spec: pl.BlockSpec, row_tiles, query_emb,
                w1_t, w1_q, b1, w2, b2, n_out: int, tile: int,
                interpret: bool, name: str) -> jax.Array:
    """The pallas_call both entry points share; they differ only in how a
    (query, tile) grid step finds its triple tile (``t_spec``, whose index
    map also takes ``last`` of :func:`_last_blocks`) and in the kernel's
    ``name``, which a profiler trace shows as its operation.
    ``row_tiles``: [Q] tiles to score per query, the rest skipped.
    Returns [Q, n_out] scores."""
    q_count = query_emb.shape[0]
    dt, h_dim = w1_t.shape
    n_tiles = n_out // tile
    # per-query first-layer bias, computed once (tiny GEMM): [Q, 1, H]
    q_bias = (jnp.dot(query_emb.astype(jnp.float32),
                      w1_q.astype(jnp.float32), precision=PRECISION)
              + b1.astype(jnp.float32))[:, None, :]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_count, n_tiles),
        in_specs=[
            t_spec,
            pl.BlockSpec((None, 1, h_dim), lambda iq, it, last: (iq, 0, 0)),
            pl.BlockSpec((dt, h_dim), lambda iq, it, last: (0, 0)),
            pl.BlockSpec((1, h_dim), lambda iq, it, last: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, 1, tile),
                               lambda iq, it, last: (iq, 0, it)),
    )
    out = pl.pallas_call(
        _score_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q_count, 1, n_out), jnp.float32),
        interpret=interpret,
        compiler_params=_scoped_vmem(tile, dt, h_dim),
        name=name,
    )(_last_blocks(row_tiles, n_tiles), triple_feats, q_bias, w1_t,
      w2.reshape(1, h_dim), b2.astype(jnp.float32))
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
    t_spec = pl.BlockSpec((tile, dt), lambda iq, it, last: (it, 0))
    every = jnp.full((query_emb.shape[0],), n // tile, jnp.int32)
    return _score_call(triple_feats, t_spec, every, query_emb, w1_t, w1_q,
                       b1, w2, b2, n, tile, interpret, "triple_score")


def _held_block(iq, it, last, *, n_tiles: int):
    """A ragged step's triple block: its own, ``(iq, it)``, up to its
    query's last (``last[iq]``, see :func:`_last_blocks`); past that, the
    one read before it, so that a skipped step fetches nothing."""
    flat = jnp.maximum(jnp.minimum(iq * n_tiles + it, last[iq]), 0)
    return flat // n_tiles, flat % n_tiles, 0


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def triple_score_batched(triple_feats: jax.Array, query_emb: jax.Array,
                         w1_t: jax.Array, w1_q: jax.Array, b1: jax.Array,
                         w2: jax.Array, b2: jax.Array,
                         n_cand: Optional[jax.Array] = None,
                         tile: int = DEFAULT_TILE,
                         interpret: bool = False) -> jax.Array:
    """Per-query candidate sets: each query scores only ITS OWN triples.

    triple_feats: [B, N, Dt]; query_emb: [B, Dq]; n_cand: optional [B]
    int32 real candidates per query -> scores [B, N].

    Same kernel body as :func:`triple_score` — the triple block is a
    squeezed (None, tile, Dt) window of query ``iq``'s own [Npad, Dt]
    slice, so weights and the per-query bias stay VMEM-resident exactly as
    in the shared-candidate variant. N is padded up to the tile size
    internally; padded rows are zero-feature triples whose scores are
    sliced off before returning. With ``n_cand``, query ``iq`` scores its
    first ``ceil(n_cand[iq] / tile)`` tiles (none for a count of 0) and
    its other slots read :data:`FILL`; the counts are traced data, so one
    compile serves them all. Without, every tile is scored. Either way a
    scored slot's value is the same per-tile dot. Callers still mask the
    slots past ``n_cand`` downstream — see
    `repro.core.router.topk_sigmoid_decision`.
    """
    b, n, dt = triple_feats.shape
    npad = _pad_to(n, tile)
    feats = jnp.pad(triple_feats, ((0, 0), (0, npad - n), (0, 0)))
    if n_cand is None:
        row_tiles = jnp.full((b,), npad // tile, jnp.int32)
    else:
        nc = jnp.clip(jnp.asarray(n_cand, jnp.int32), 0, n)
        row_tiles = (nc + tile - 1) // tile
    t_spec = pl.BlockSpec((None, tile, dt), functools.partial(
        _held_block, n_tiles=npad // tile))
    out = _score_call(feats, t_spec, row_tiles, query_emb, w1_t, w1_q, b1,
                      w2, b2, npad, tile, interpret, "triple_score_batched")
    return out[:, :n]
