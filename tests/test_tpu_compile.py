"""The main path's Pallas kernels compile for a TPU v5e at bench widths.

Each case lowers one kernel with ``interpret=False`` against a described
(not attached) ``v5e:2x2`` topology and compiles it with the TPU compiler,
which refuses what interpret mode accepts: block shapes off the (8, 128)
tiling, ops Mosaic has no lowering for, SMEM or VMEM overflow.  The
compiled text must contain the Mosaic custom call.  Nothing runs.

The topology is described only inside the module fixture: describing it
loads the TPU library, which one process at a time may hold.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import rtree_join, rtree_knn, rtree_select

N, F, B, C = 4096, 64, 64, 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _d1(s):
    return [s((N, F), jnp.float32)] * 4 + [s((N, F), jnp.int32)]


def _d3(s):
    return ([s((N, F), jnp.uint16)] * 2 + [s((N, 2), jnp.float32)] * 2
            + [s((N, F), jnp.int32)])


def _cases(s):
    ids, q4, q2 = s((B, C), jnp.int32), s((B, 4), jnp.float32), \
        s((B, 2), jnp.float32)
    p = 2048
    meta = [s((p,), jnp.int32)] * 3 + [s((p, F // 8), jnp.int32)]
    coords = [s((N, 4, F), jnp.float32)] * 2
    return {
        "select_score": (functools.partial(
            rtree_select.select_level_masks, interpret=False),
            [ids, q4] + _d1(s)),
        "select_fused": (functools.partial(
            rtree_select.select_level_fused, cap=4096, interpret=False),
            [ids, q4] + _d1(s)),
        # the root level: a one-node frontier makes one-row tiles
        "select_fused_root": (functools.partial(
            rtree_select.select_level_fused, cap=4096, interpret=False),
            [s((B, 1), jnp.int32), q4] + _d1(s)),
        "select_score_d3": (functools.partial(
            rtree_select.select_level_masks_d3, interpret=False),
            [ids, q4] + _d3(s)),
        "knn_score": (functools.partial(
            rtree_knn.knn_level_dists, interpret=False),
            [ids, q2] + _d1(s)),
        "knn_fused_leaf": (functools.partial(
            rtree_knn.knn_leaf_fused, k=8, interpret=False),
            [ids, q2] + _d1(s)),
        "join_pair_masks": (functools.partial(
            rtree_join.join_pair_masks, to=8, ti=128, interpret=False),
            meta + coords),
    }


@pytest.mark.parametrize("name", ["select_score", "select_fused",
                                  "select_fused_root", "select_score_d3",
                                  "knn_score",
                                  "knn_fused_leaf", "join_pair_masks"])
def test_kernel_compiles_for_v5e(one_chip, name):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _cases(s)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
