"""Compile the main path's Pallas kernels for a TPU v5e chip that is
described, not attached — what interpret mode cannot check: the (8, 128)
tiling rule, 2-D vector layouts and VMEM limits.

Widths are the deployment's: a 65,536-point phase-1 shard in 512-point
tiles (the block-sparse pair lists at their full T² length, walked by a
grid as long as the active count) and a 128-slot × 128-vertex phase-2
merge matrix.  The topology is described inside a fixture, never at
import, so every test worker collects the same tests and only the worker
given this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import contour_dist as cd
from repro.kernels import pairwise_dist as pd

N, BT = 65_536, 512
PAIRS = (N // BT) ** 2
SLOTS, VERTS = 128, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _kernel_case(name, shape):
    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    x, mask, ints, eps = shape((N, 2), f32), shape((N,), b), shape((N,), i32), \
        shape((), f32)
    pairs = (shape((PAIRS,), i32),) * 3
    n_active = shape((), i32)
    cases = {
        "neighbor_count": (pd.neighbor_count, (x, mask, eps)),
        "min_label_sweep": (pd.min_label_sweep, (x, mask, ints, mask, eps)),
        "neighbor_count_sparse": (
            lambda *a: pd.neighbor_count_sparse(*a, PAIRS, bt=BT),
            (x, mask, eps) + pairs),
        "min_label_sweep_sparse": (
            lambda *a: pd.min_label_sweep_sparse(*a, PAIRS, bt=BT),
            (x, mask, ints, mask, eps) + pairs),
        # The grid bound as the phase-1 path gives it: a traced scalar.
        "sparse_traced_grid": (
            lambda *a: (pd.neighbor_count_sparse(a[0], a[1], a[4], *a[5:], bt=BT),
                        pd.min_label_sweep_sparse(*a, bt=BT)),
            (x, mask, ints, mask, eps) + pairs + (n_active,)),
        "contour_min_d2": (
            lambda p, v: cd.contour_min_d2(p, v, VERTS),
            (shape((SLOTS * VERTS, 2), f32), shape((SLOTS * VERTS,), i32))),
    }
    return cases[name]


@pytest.mark.parametrize("name", [
    "neighbor_count", "min_label_sweep", "neighbor_count_sparse",
    "min_label_sweep_sparse", "sparse_traced_grid", "contour_min_d2",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    fn, args = _kernel_case(name, shape)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
