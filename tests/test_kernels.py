"""Per-kernel shape/dtype sweeps vs ref.py oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypothesis_compat import given, st


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,s,d,bq,bk", [
    (1, 2, 1, 32, 16, 16, 16),
    (2, 4, 2, 64, 32, 16, 32),
    (1, 8, 8, 128, 64, 64, 64),   # MHA
])
def test_flash_attention_sweep(b, h, kv, s, d, bq, bk, dtype):
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    q = jax.random.normal(jax.random.key(0), (b, h, s, d), dtype)
    k = jax.random.normal(jax.random.key(1), (b, kv, s, d), dtype)
    v = jax.random.normal(jax.random.key(2), (b, kv, s, d), dtype)
    out = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    ref = attention_ref(q, k, v)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kv_len", [1, 33, 96, 128])
def test_decode_attention_sweep(kv_len, dtype):
    from repro.kernels.decode_attention.kernel import decode_attention
    from repro.kernels.decode_attention.ref import decode_ref
    B, H, KV, S, D = 2, 8, 4, 128, 32
    q = jax.random.normal(jax.random.key(0), (B, H, D), dtype)
    k = jax.random.normal(jax.random.key(1), (B, KV, S, D), dtype)
    v = jax.random.normal(jax.random.key(2), (B, KV, S, D), dtype)
    out = decode_attention(q, k, v, jnp.int32(kv_len), block_k=32,
                           interpret=True)
    ref = decode_ref(q, k, v, jnp.int32(kv_len))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("n,dt,dq,h,q", [(128, 20, 8, 32, 1),
                                         (256, 36, 24, 64, 3),
                                         (512, 114, 32, 128, 2)])
def test_triple_score_sweep(n, dt, dq, h, q):
    from repro.kernels.triple_score.kernel import triple_score
    from repro.kernels.triple_score.ref import triple_score_ref
    ks = jax.random.split(jax.random.key(0), 7)
    args = (jax.random.normal(ks[0], (n, dt)),
            jax.random.normal(ks[1], (q, dq)),
            jax.random.normal(ks[2], (dt, h)) * 0.2,
            jax.random.normal(ks[3], (dq, h)) * 0.2,
            jax.random.normal(ks[4], (h,)) * 0.1,
            jax.random.normal(ks[5], (h, 1)) * 0.2,
            jax.random.normal(ks[6], (1,)))
    out = triple_score(*args, tile=64, interpret=True)
    np.testing.assert_allclose(out, triple_score_ref(*args),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tile,n", [(64, 300), (128, 300), (128, 512)])
def test_triple_score_batched_skips_tiles_past_each_count(tile, n):
    """Ragged grid: a row scores its first ceil(count / tile) tiles with the
    full grid's arithmetic, bit for bit; its other slots read the fill.
    Rows without candidates sit at the start, in the middle and in a run
    at the end, where their steps hold an earlier row's block."""
    from repro.kernels.triple_score import kernel
    from repro.kernels.triple_score.ref import triple_score_batched_ref
    counts = np.asarray([0, 1, tile - 1, 0, tile, tile + 1, n // 2 + 3, n,
                         0, 0], np.int32)
    b, dt, dq, h = len(counts), 36, 24, 64
    ks = jax.random.split(jax.random.key(tile + n), 7)
    args = (jax.random.normal(ks[0], (b, n, dt)),
            jax.random.normal(ks[1], (b, dq)),
            jax.random.normal(ks[2], (dt, h)) * 0.2,
            jax.random.normal(ks[3], (dq, h)) * 0.2,
            jax.random.normal(ks[4], (h,)) * 0.1,
            jax.random.normal(ks[5], (h, 1)) * 0.2,
            jax.random.normal(ks[6], (1,)))
    full = np.asarray(kernel.triple_score_batched(*args, tile=tile,
                                                  interpret=True))
    np.testing.assert_allclose(full, triple_score_batched_ref(*args),
                               rtol=1e-5, atol=1e-5)
    compiled = kernel.triple_score_batched._cache_size()
    every = np.asarray(kernel.triple_score_batched(
        *args, n_cand=jnp.full(b, n, jnp.int32), tile=tile, interpret=True))
    ragged = np.asarray(kernel.triple_score_batched(
        *args, n_cand=jnp.asarray(counts), tile=tile, interpret=True))
    # the counts are data: both count vectors ran one compiled program
    assert kernel.triple_score_batched._cache_size() == compiled + 1
    np.testing.assert_array_equal(every, full)
    scored = -(-counts // tile) * tile
    for row, stop in enumerate(scored):
        np.testing.assert_array_equal(ragged[row, :stop], full[row, :stop])
        assert (ragged[row, stop:] == kernel.FILL).all()


@given(st.integers(1, 30), st.integers(5, 100), st.integers(0, 100))
def test_skew_metrics_property(rows, k, seed):
    from repro.kernels.skew_metrics.kernel import skew_metrics
    from repro.kernels.skew_metrics.ref import skew_metrics_ref
    rng = np.random.default_rng(seed)
    scores = np.sort(rng.uniform(0.01, 1, (rows, k)).astype(np.float32),
                     axis=1)[:, ::-1]
    out = skew_metrics(jnp.asarray(scores), interpret=True)
    ref = skew_metrics_ref(jnp.asarray(scores))
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("b,nnz,d,tile", [(8, 4, 16, 4), (16, 8, 32, 8),
                                          (32, 2, 64, 8)])
def test_segment_reduce_sweep(b, nnz, d, tile):
    from repro.kernels.segment_reduce.kernel import segment_sum_sorted
    from repro.kernels.segment_reduce.ref import segment_sum_sorted_ref
    rows = jax.random.normal(jax.random.key(0), (b * nnz, d))
    seg = jnp.repeat(jnp.arange(b), nnz)
    out = segment_sum_sorted(rows, seg, b, nnz, seg_tile=tile, interpret=True)
    ref = segment_sum_sorted_ref(rows, seg, b)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_embedding_bag_fused_vs_model_embedding_bag():
    from repro.kernels.segment_reduce.ops import embedding_bag_fused
    from repro.models.recsys import embedding_bag
    table = jax.random.normal(jax.random.key(0), (64, 8))
    ids = jax.random.randint(jax.random.key(1), (8, 4), -1, 64)
    a = embedding_bag_fused(table, ids, 8)
    b = embedding_bag(table, ids)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
