"""Ahead-of-time compiles of the served path for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, installed alongside JAX,
compiles for a ``v5e:2x2`` topology that is described, not attached.  It
refuses what the chip would refuse — Pallas block shapes off the (8, 128)
tile, kernels that cannot be laid out, programs over the device's HBM —
which interpret-mode tests cannot show.  The topology is described inside
a module fixture (never at import), so only the worker that runs this file
loads the TPU library.  The settings force ``fused_selector="pallas"``:
``"auto"`` would see this process's CPU backend and pick the unfused
program.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

# 16 GiB of HBM on one v5e chip.
V5E_HBM_BYTES = 16 * 2**30
# The paper's selector (la=2, k_gh=3, 10 trees of depth 4, exact refit)
# over the 384-point TensorFlow space (F=5, T=7): the served smoke's shape.
M, F, K = 384, 5, 3
N_TREES, DEPTH = 10, 4
SMOKE_LANE_SLOTS = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("s_dim,emit_full", [(1, True), (M * K, False),
                                             (M * K * K, False)],
                         ids=["root", "la1_level", "la2_level"])
def test_fused_select_step_lowers_for_v5e(one_chip, s_dim, emit_full):
    from repro.kernels.select_step.kernel import select_step_call
    width, leaves = 2 ** (DEPTH - 1), 2 ** DEPTH
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                sharding=one_chip)
    args = (sd((s_dim, N_TREES, DEPTH, width), jnp.int32),
            sd((s_dim, N_TREES, DEPTH, width), jnp.float32),
            sd((s_dim, N_TREES, leaves), jnp.float32),
            sd((s_dim, M), jnp.float32), sd((s_dim, M), jnp.bool_),
            sd((s_dim,), jnp.float32), sd((s_dim,), jnp.float32),
            sd((M, F), jnp.float32), sd((M,), jnp.float32),
            sd((), jnp.float32), sd((), jnp.float32), sd((K,), jnp.float32))

    def step(*a, cens):
        return select_step_call(*a, cens=cens, emit_full=emit_full,
                                want_nodes=True)

    compiled = jax.jit(step).lower(
        *args, cens=sd((s_dim, M), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_la2_segment_fits_one_v5e(one_chip):
    """The served segment program at the smoke's lane count, with the
    compiled Pallas selector, compiles for one v5e and fits its HBM."""
    from repro.core import Settings
    from repro.core.optimizer import _episode_segment
    from repro.jobs import tensorflow_jobs
    from repro.service import ServiceConfig
    from repro.service.engine import SegmentEngine

    jobs = tensorflow_jobs(0)
    assert jobs[0].space.n_points == M
    s = Settings(policy="lynceus", la=2, k_gh=K, n_trees=N_TREES,
                 depth=DEPTH, refit="exact", timeout=True,
                 fused_selector="pallas")
    engine = SegmentEngine(jobs, s, ServiceConfig(
        lane_slots=SMOKE_LANE_SLOTS))
    *args, settings = engine.segment_args()
    compiled = _episode_segment.lower(*_abstract(args, one_chip),
                                      settings).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < V5E_HBM_BYTES, f"{total / 2**30:.2f} GiB"
