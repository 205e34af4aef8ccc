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


def test_triple_score_shared_candidates_compiles(one_chip):
    text = _compiled_text(
        lambda *a: score_kernel.triple_score(*a, interpret=False),
        _shape(one_chip, (512, 110)), _shape(one_chip, (3, 32)),
        *_scorer_shapes(one_chip, 110, 32, 128))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b", [8, 1024])
def test_skew_metrics_ragged_compiles(one_chip, b):
    text = _compiled_text(
        lambda s, nv: skew_kernel.skew_metrics(s, nv, interpret=False),
        _shape(one_chip, (b, 100)), _shape(one_chip, (b,), jnp.int32))
    assert "tpu_custom_call" in text


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
