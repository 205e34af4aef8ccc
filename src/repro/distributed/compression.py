"""Gradient compression for the cross-pod (DCN) reduction.

On the multi-pod mesh the ``pod`` axis crosses data-center network, not
ICI; a bf16 all-reduce there costs ~25 GB/step for arctic-480b. This
module implements the standard mitigation: **int8 block-quantized
all-gather with error feedback** —

  1. residual-corrected grad  g' = g + e   (error feedback buffer e)
  2. per-block (128) absmax scales; int8 quantize
  3. all_gather(int8) over the pod axis (half the bytes of bf16,
     quarter of f32), dequantize, mean
  4. e <- g' - dequant(quant(g'))  (what compression lost, re-injected
     next step — keeps SGD convergence, Karimireddy et al. 2019)

Exposed as a shard_map transform over a per-pod-grads function, plus
raw quantize/dequantize utilities (property-tested in
tests/test_compression.py: error-feedback residual decays the bias).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

BLOCK = 128


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-128-block absmax int8 quantization. Returns (q, scales)."""
    flat = x.reshape(-1)
    pad = -flat.shape[0] % BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, BLOCK).astype(jnp.float32)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale[:, 0]


def dequantize_int8(q: jax.Array, scale: jax.Array, shape, dtype) -> jax.Array:
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return flat[:n].reshape(shape).astype(dtype)


def compress_decompress(x: jax.Array) -> jax.Array:
    q, s = quantize_int8(x)
    return dequantize_int8(q, s, x.shape, x.dtype)


def apply_error_feedback(grads: Any, residual: Any) -> tuple[Any, Any]:
    """(grads+residual, new_residual) after a quantize/dequantize round."""
    corrected = jax.tree.map(lambda g, e: g + e.astype(g.dtype), grads, residual)
    compressed = jax.tree.map(compress_decompress, corrected)
    new_residual = jax.tree.map(lambda c, q: (c - q).astype(jnp.float32),
                                corrected, compressed)
    return compressed, new_residual


def cross_pod_mean_int8(mesh, axis: str = "pod"):
    """shard_map transform: int8 all-gather mean over the pod axis.

    Input: per-pod gradient pytree (replicated within the pod, distinct
    across pods). Output: cross-pod mean, computed by exchanging int8.
    """
    def transform(grads: Any) -> Any:
        def body(g_tree):
            def one(g):
                q, s = quantize_int8(g)
                qg = jax.lax.all_gather(q, axis)          # [pods, blocks, B]
                sg = jax.lax.all_gather(s, axis)
                deq = jax.vmap(lambda qq, ss: dequantize_int8(
                    qq, ss, g.shape, jnp.float32))(qg, sg)
                return jnp.mean(deq, axis=0).astype(g.dtype)
            return jax.tree.map(one, g_tree)

        specs = jax.tree.map(lambda _: P(), grads)
        # replication check off: the int8 gather+mean provably replicates
        # the result across the pod axis, but the varying-manual-axes
        # checker can't see through the quantize/dequantize round trip.
        return jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                             out_specs=specs, check_vma=False)(grads)
    return transform
