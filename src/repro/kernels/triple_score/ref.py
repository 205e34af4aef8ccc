"""Pure-jnp oracle for the fused triple scorer.

Every dot runs at ``Precision.HIGHEST``, as the kernel's do (see the
precision note in `kernel.py`): on a TPU both sides then compute in full
f32 and agree to f32 rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.triple_score.kernel import PRECISION

_mm = functools.partial(jnp.matmul, precision=PRECISION)


def triple_score_ref(triple_feats, query_emb, w1_t, w1_q, b1, w2, b2):
    """[N,Dt] x [Q,Dq] -> [Q,N] 2-layer-MLP relevance scores."""
    t32 = triple_feats.astype(jnp.float32)
    q32 = query_emb.astype(jnp.float32)
    h = _mm(t32, w1_t.astype(jnp.float32))[None, :, :] \
        + (_mm(q32, w1_q.astype(jnp.float32)) + b1)[:, None, :]
    h = jax.nn.relu(h)
    return _mm(h, w2.astype(jnp.float32))[..., 0] + b2[0]


def triple_score_batched_ref(triple_feats, query_emb, w1_t, w1_q, b1, w2, b2):
    """Per-query candidates: [B,N,Dt] x [B,Dq] -> [B,N] scores."""
    t32 = triple_feats.astype(jnp.float32)
    q32 = query_emb.astype(jnp.float32)
    h = _mm(t32, w1_t.astype(jnp.float32)) \
        + (_mm(q32, w1_q.astype(jnp.float32)) + b1)[:, None, :]
    h = jax.nn.relu(h)
    return _mm(h, w2.astype(jnp.float32))[..., 0] + b2[0]


def triple_score_parts_ref(heads, rels, tails, hops, query_emb, w1_t, w1_q,
                           b1, w2, b2):
    """The feature block's parts -> [B,N] scores, as the sum of partial
    first-layer matmuls over ``W1_t``'s row blocks (see
    `kernel.triple_score_parts`): [B,N,d] rows, [B,N,2] hop distances,
    [B,d] queries."""
    h, r, t = (x.astype(jnp.float32) for x in (heads, rels, tails))
    q32 = query_emb.astype(jnp.float32)
    d = h.shape[-1]
    w = w1_t.astype(jnp.float32)
    m = (w.shape[0] - 3 * d - 4) // 2
    eye = jnp.eye(m, dtype=jnp.float32)
    qv = q32 / jnp.sqrt(jnp.float32(d))
    sims = jnp.stack([jnp.einsum("bnd,bd->bn", x, qv, precision=PRECISION)
                      for x in (h, r, t, h + r)], axis=-1)
    pre = (_mm(h, w[:d]) + _mm(r, w[d:2 * d]) + _mm(t, w[2 * d:3 * d])
           + _mm(eye[hops[..., 0]], w[3 * d:3 * d + m])
           + _mm(eye[hops[..., 1]], w[3 * d + m:3 * d + 2 * m])
           + _mm(sims, w[3 * d + 2 * m:])
           + (_mm(q32, w1_q.astype(jnp.float32)) + b1)[:, None, :])
    return _mm(jax.nn.relu(pre), w2.astype(jnp.float32))[..., 0] + b2[0]
