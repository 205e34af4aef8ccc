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
