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

Parts entry (`triple_score_parts`, the question path): the features
arrive as the block's parts, never concatenated in HBM — head, relation
and tail rows ([B, N, d] each, three (None, tile, d) blocks) and the hop
distances ([B, N, 2] int32, a (None, tile, 2) block), all four on the
same ragged index map. W1_t is read as two resident blocks of the one
array: its first 3d rows (a (3d, H) block at (0, 0); d a multiple of 8)
and its last 2m + 4 (the DDE and similarity rows, sliced outside). The
query enters twice, as its bias and as q / sqrt(d), each a (None, 1, ·)
block. Per tile the kernel builds [onehot(dh) | onehot(dt) | sims] in
VMEM, the similarities as f32 lane reductions of the tiles it holds.
VMEM at gte-large width ≈ 31 MiB, about the block kernel's: the parts of
W1_t add up to its 12 MiB (`_parts_vmem`).

Precision: every dot here and in the XLA reference (`ref.py`) runs at
``Precision.HIGHEST``. A TPU f32 dot at default precision is a single bf16
pass, and two programs that round differently can swap near-tied
candidates in the top-k; full f32 on both sides keeps them within f32
rounding of each other. The parts entry's similarities are f32 products
and sums on the vector unit, full f32 too.
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


def _scoped_vmem(block_elems: int, tile: int, h_dim: int, n_acts: int = 2):
    """Compiler parameters that raise the scoped-VMEM limit where a
    kernel's blocks need more than the default: ``block_elems`` float32
    elements of blocks, each held twice (the pipeline's two buffers, the
    resident weights included), and ``n_acts`` ``[tile, H]`` activations,
    with a quarter for Mosaic's own scratch. At gte-large width (Dt=3086,
    H=1024) ``W1_t`` alone is 12 MiB, and the default 16 MiB does not hold
    two of it. None: the default holds."""
    need = int(1.25 * (2 * block_elems + n_acts * tile * h_dim) * 4)
    if need <= DEFAULT_SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(need, MAX_SCOPED_VMEM))


def _query_bias(query_emb, w1_q, b1) -> jax.Array:
    """[Q, Dq] -> [Q, 1, H]: each query's first-layer bias, computed once
    (tiny GEMM) and held in VMEM while its tiles stream."""
    return (jnp.dot(query_emb.astype(jnp.float32), w1_q.astype(jnp.float32),
                    precision=PRECISION)
            + b1.astype(jnp.float32))[:, None, :]


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
    q_bias = _query_bias(query_emb, w1_q, b1)
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
        compiler_params=_scoped_vmem(
            tile * dt + dt * h_dim + 2 * h_dim + tile, tile, h_dim),
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


def _row_tiles(n_cand: Optional[jax.Array], b: int, n: int,
               tile: int) -> jax.Array:
    """[B] tiles each row scores: ``ceil(n_cand / tile)`` of its count,
    clipped to 0 .. N; without counts, every tile of N padded to ``tile``."""
    if n_cand is None:
        return jnp.full((b,), _pad_to(n, tile) // tile, jnp.int32)
    nc = jnp.clip(jnp.asarray(n_cand, jnp.int32), 0, n)
    return (nc + tile - 1) // tile


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
    row_tiles = _row_tiles(n_cand, b, n, tile)
    t_spec = pl.BlockSpec((None, tile, dt), functools.partial(
        _held_block, n_tiles=npad // tile))
    out = _score_call(feats, t_spec, row_tiles, query_emb, w1_t, w1_q, b1,
                      w2, b2, npad, tile, interpret, "triple_score_batched")
    return out[:, :n]


def _parts_kernel(last_ref, h_ref, r_ref, t_ref, hops_ref, qv_ref, qb_ref,
                  w_ref, wx_ref, w2_ref, b2_ref, o_ref):
    iq, it = pl.program_id(0), pl.program_id(1)
    scored = iq * pl.num_programs(1) + it <= last_ref[iq]

    @pl.when(scored)
    def _():
        d = h_ref.shape[-1]
        h = h_ref[...].astype(jnp.float32)         # [tile, d] each
        r = r_ref[...].astype(jnp.float32)
        t = t_ref[...].astype(jnp.float32)

        def mm(x, w):
            return jax.lax.dot(x, w.astype(jnp.float32), precision=PRECISION,
                               preferred_element_type=jnp.float32)
        pre = (mm(h, w_ref[0:d, :]) + mm(r, w_ref[d:2 * d, :])
               + mm(t, w_ref[2 * d:3 * d, :]) + qb_ref[...])
        # the DDE one-hots and the four similarities, as one [tile, 2m + 4]
        # block in VMEM, against the last 2m + 4 rows of W1_t
        wx = wx_ref[...].astype(jnp.float32)       # [2m + 4, H]
        m = (wx.shape[0] - 4) // 2
        hops = hops_ref[...]                       # [tile, 2] int32
        lane = jax.lax.broadcasted_iota(jnp.int32, (h.shape[0], wx.shape[0]),
                                        1)
        small = ((lane == hops[:, 0:1]) | (lane == hops[:, 1:2] + m)
                 ).astype(jnp.float32)
        qv = qv_ref[...]                           # [1, d], q / sqrt(d)
        for j, x in enumerate((h, r, t, h + r)):
            sim = jnp.sum(x * qv, axis=1, keepdims=True)   # [tile, 1]
            small = jnp.where(lane == 2 * m + j, sim, small)
        hid = jnp.maximum(pre + mm(small, wx), 0.0)
        w2 = w2_ref[...].astype(jnp.float32)       # [1, H]
        score = jax.lax.dot_general(w2, hid, _NT, precision=PRECISION,
                                    preferred_element_type=jnp.float32)
        o_ref[...] = score + b2_ref[0]             # [1, tile]

    @pl.when(jnp.logical_not(scored))
    def _():
        o_ref[...] = jnp.full(o_ref.shape, FILL, o_ref.dtype)


def _parts_vmem(tile: int, d: int, h_dim: int, n_x: int):
    """:func:`_scoped_vmem` of :func:`triple_score_parts`'s blocks as VMEM
    lays them out, the hops' 2 lanes and the one-row blocks' one sublane
    padded to an (8, 128) tile, and four ``[tile, H]`` activations (the
    partial products and their sum). At gte-large width (d = H = 1024)
    about what the block kernel asks: the two parts of ``W1_t`` add up to
    its 12 MiB."""
    blocks = (3 * tile * d + tile * 128 + 8 * d + 2 * 8 * h_dim
              + (3 * d + _pad_to(n_x, 8)) * h_dim + 8 * tile)
    return _scoped_vmem(blocks, tile, h_dim, n_acts=4)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def triple_score_parts(heads: jax.Array, rels: jax.Array, tails: jax.Array,
                       hops: jax.Array, query_emb: jax.Array,
                       w1_t: jax.Array, w1_q: jax.Array, b1: jax.Array,
                       w2: jax.Array, b2: jax.Array,
                       n_cand: Optional[jax.Array] = None,
                       tile: int = DEFAULT_TILE,
                       interpret: bool = False) -> jax.Array:
    """Per-query candidate sets given as the feature block's parts: the
    scores of :func:`triple_score_batched` on ``[h | r | t | onehot(dh) |
    onehot(dt) | sims]``, with no such block in HBM.

    heads, rels, tails: [B, N, d] rows; hops: [B, N, 2] int32 hop
    distances of head and tail, each in 0 .. m - 1; query_emb: [B, d];
    w1_t: [3d + 2m + 4, H], rows in the block's order -> scores [B, N].
    The similarities are ``x . q / sqrt(d)`` for x = h, r, t, h + r.

    Each tile's first layer is ``h @ W_h + r @ W_r + t @ W_t`` (the
    first 3d rows of ``W1_t``, one resident block) plus ``[onehot(dh) |
    onehot(dt) | sims] @ W_x`` (its last 2m + 4 rows), the one-hots and
    similarities built in VMEM from the tile's own hops and rows, plus
    the query bias. The grid, ``n_cand`` and :data:`FILL` are as in
    :func:`triple_score_batched`; every streamed part follows the same
    held-block index map, so a skipped step fetches none of them. On a
    TPU d must be a multiple of 8 (the row blocks of ``W1_t``).
    """
    b, n, d = heads.shape
    h_dim = w1_t.shape[1]
    n_x = w1_t.shape[0] - 3 * d
    npad = _pad_to(n, tile)
    n_tiles = npad // tile

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, npad - n), (0, 0)))
    held = functools.partial(_held_block, n_tiles=n_tiles)
    qv = (query_emb.astype(jnp.float32) / jnp.sqrt(jnp.float32(d)))[:, None, :]

    def resident(shape):
        return pl.BlockSpec(shape, lambda iq, it, last: (0, 0))

    def per_query(width):
        return pl.BlockSpec((None, 1, width), lambda iq, it, last: (iq, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_tiles),
        in_specs=[pl.BlockSpec((None, tile, d), held)] * 3 + [
            pl.BlockSpec((None, tile, 2), held),
            per_query(d), per_query(h_dim),
            resident((3 * d, h_dim)), resident((n_x, h_dim)),
            resident((1, h_dim)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, 1, tile),
                               lambda iq, it, last: (iq, 0, it)),
    )
    out = pl.pallas_call(
        _parts_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, npad), jnp.float32),
        interpret=interpret,
        compiler_params=_parts_vmem(tile, d, h_dim, n_x),
        name="triple_score_parts",
    )(_last_blocks(_row_tiles(n_cand, b, n, tile), n_tiles), pad(heads),
      pad(rels), pad(tails), pad(hops.astype(jnp.int32)), qv,
      _query_bias(query_emb, w1_q, b1),
      w1_t, w1_t[3 * d:], w2.reshape(1, h_dim), b2.astype(jnp.float32))
    return out[:, 0, :n]
