"""AOT compiles of the routing path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed alongside JAX, compiles
for a chip that is described and not attached, and refuses what the chip
would refuse (block shapes off the (8, 128) tiling, primitives Mosaic
cannot lower). Interpret-mode tests accept both. Each case asserts that
the compiled program holds the kernel (``tpu_custom_call``), so a kernel
that silently fell back to XLA fails too.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import router
from repro.kernels.skew_metrics import kernel as skew_kernel
from repro.kernels.triple_score import kernel as score_kernel


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _kernel_ops(text: str) -> list[str]:
    """Names of the compiled program's kernel operations."""
    return re.findall(r"%([\w-]+?)(?:\.\d+)? = [^\n]*tpu_custom_call", text)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _scorer_shapes(sharding, dt, dq, h):
    """(w1_t, w1_q, b1, w2, b2) in the kernel's argument order."""
    return tuple(_shape(sharding, s)
                 for s in ((dt, h), (dq, h), (h,), (h, 1), (1,)))


@pytest.mark.parametrize("b,n,dt,dq,h", [
    (64, 512, 110, 32, 128),       # ScorerConfig() widths
    (8, 4096, 1156, 32, 1024),     # paper-scale SubgraphRAG widths
])
def test_triple_score_batched_compiles(one_chip, b, n, dt, dq, h):
    text = _compiled_text(
        lambda *a: score_kernel.triple_score_batched(*a, interpret=False),
        _shape(one_chip, (b, n, dt)), _shape(one_chip, (b, dq)),
        *_scorer_shapes(one_chip, dt, dq, h))
    assert "tpu_custom_call" in text
    assert _kernel_ops(text) == ["triple_score_batched"]


def test_ragged_triple_score_batched_compiles_at_gte_large_width(one_chip):
    """The ragged grid at bucket 64, Dt = 3086 and 512 candidates: the
    per-row counts are an operand given by its shape alone, so every count
    vector runs this one executable."""
    b, n, d = 64, 512, 1024
    dt = 3 * d + 14
    text = _compiled_text(
        lambda *a: score_kernel.triple_score_batched(*a[:7], n_cand=a[7],
                                                     interpret=False),
        _shape(one_chip, (b, n, dt)), _shape(one_chip, (b, d)),
        *_scorer_shapes(one_chip, dt, d, d),
        _shape(one_chip, (b,), jnp.int32))
    assert _kernel_ops(text) == ["triple_score_batched"]


def test_triple_score_parts_compiles_at_gte_large_width(one_chip):
    """The question path's scorer at the cell's widths (bucket 64, 512
    candidates, d = H = 1024, tile 128): the feature block's parts in,
    the ragged grid, and the scoped VMEM it asks for, which the default
    16 MiB does not hold."""
    b, n, d, tile = 64, 512, 1024, 128
    dt = 3 * d + 14
    text = _compiled_text(
        lambda *a: score_kernel.triple_score_parts(
            *a[:10], n_cand=a[10], tile=tile, interpret=False),
        *[_shape(one_chip, (b, n, d))] * 3,
        _shape(one_chip, (b, n, 2), jnp.int32), _shape(one_chip, (b, d)),
        *_scorer_shapes(one_chip, dt, d, d),
        _shape(one_chip, (b,), jnp.int32))
    assert _kernel_ops(text) == ["triple_score_parts"]
    limit = score_kernel._parts_vmem(tile, d, d, dt - 3 * d).vmem_limit_bytes
    assert limit > score_kernel.DEFAULT_SCOPED_VMEM
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line)
    assert f'"size":"{limit}"' in call


def test_triple_score_shared_candidates_compiles(one_chip):
    text = _compiled_text(
        lambda *a: score_kernel.triple_score(*a, interpret=False),
        _shape(one_chip, (512, 110)), _shape(one_chip, (3, 32)),
        *_scorer_shapes(one_chip, 110, 32, 128))
    assert "tpu_custom_call" in text
    assert _kernel_ops(text) == ["triple_score"]


@pytest.mark.parametrize("b", [8, 1024])
def test_skew_metrics_ragged_compiles(one_chip, b):
    text = _compiled_text(
        lambda s, nv: skew_kernel.skew_metrics(s, nv, interpret=False),
        _shape(one_chip, (b, 100)), _shape(one_chip, (b,), jnp.int32))
    assert "tpu_custom_call" in text
    assert _kernel_ops(text) == ["skew_metrics"]


def test_fused_retrieve_to_decision_compiles(one_chip):
    """The whole fused program `route_retrieved` runs at B=64, N=512."""
    def program(feats, qemb, w1_t, w1_q, b1, w2, b2, thr, n_cand):
        return router._retrieved_program(
            feats, qemb, w1_t, w1_q, b1, w2, b2, thr, n_cand, top_k=100,
            metric="gini", p_cdf=0.95, ragged=True, use_kernels=True,
            interpret=False, tile=128)
    text = _compiled_text(
        program, _shape(one_chip, (64, 512, 110)), _shape(one_chip, (64, 32)),
        *_scorer_shapes(one_chip, 110, 32, 128), _shape(one_chip, (1,)),
        _shape(one_chip, (64,), jnp.int32))
    assert text.count("tpu_custom_call") >= 2  # scoring + skew kernels
    assert sorted(_kernel_ops(text)) == ["skew_metrics",
                                         "triple_score_batched"]


def test_question_program_compiles_at_gte_large_width(one_chip):
    """`route_kg` at bucket 64 over a store of NSM's WebQSP Freebase size
    (1,441,420 x 1,024 entity table): the gathers, the scorer over the
    feature parts at Dt=3086 (whose blocks need more than the default
    scoped VMEM) and the skew kernel, in one program, with no
    [64, 512, 3086] feature block."""
    n_ent, n_rel, n_tri, d, b, n = 1441420, 6102, 11_300_000, 1024, 64, 512
    dt = 3 * d + 14

    def program(*args):
        return router._kg_program(*args, top_k=100, metric="gini",
                                  p_cdf=0.95, use_kernels=True,
                                  interpret=False, tile=128)
    text = _compiled_text(
        program, _shape(one_chip, (n_ent, d)), _shape(one_chip, (n_rel, d)),
        *[_shape(one_chip, (n_tri,), jnp.int32)] * 3,
        *_scorer_shapes(one_chip, dt, d, d), _shape(one_chip, (1,)),
        _shape(one_chip, (b,), jnp.int32), _shape(one_chip, (b, d)),
        _shape(one_chip, (b, n), jnp.int32), _shape(one_chip, (b,), jnp.int32))
    assert sorted(_kernel_ops(text)) == ["skew_metrics",
                                         "triple_score_parts"]
    assert not re.search(rf"= f32\[{b},{n},{dt}\]", text)
