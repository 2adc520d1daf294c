"""Ahead-of-time compiles of the aggregation kernels for a described TPU
v5e chip (no chip attached): the TPU compiler refuses block shapes that
break the tiling rules and scalar memory beyond 1 MiB, which interpret mode
never checks.

Shapes are the trainer's largest layer on `reddit-like` at batch 1024,
fanout (10, 10, 10): the calibrated caps are (7808, 17536, 20480) for
COMM-RAND-MIX-12.5% and (8320, 21248, 23040) for the uniform eval policy,
so the widest layer gathers n_dst = 21248 rows of r = 10 from
n_src = 23040. Widths cover reddit-like's features (64), the hidden width
(256), Reddit's features (602), and `gat_res`'s head-folded rows on
ogbn-products: 128 per hidden head, 47 (the classes) per output head.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gather_agg.kernel import (gather_agg_bwd_dw_pallas,
                                             gather_agg_bwd_dx_pallas,
                                             gather_agg_fwd_pallas)
from repro.kernels.gather_cached.kernel import gather_cached_fwd_pallas

N_DST, N_SRC, R = 21248, 23040, 10
N_FEATS, N_CACHE = 20000, 4000        # reddit-like rows, 20% cache


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernels(f):
    """name -> (fn, arg shapes) of each kernel at width f."""
    i32, f32 = jnp.int32, jnp.float32
    return {
        "gather_agg_fwd": (gather_agg_fwd_pallas,
                           [((N_SRC, f), f32), ((N_DST, R), i32),
                            ((N_DST, R), f32)]),
        "gather_agg_bwd_dx": (
            lambda idx, w, g: gather_agg_bwd_dx_pallas(idx, w, g, N_SRC),
            [((N_DST, R), i32), ((N_DST, R), f32), ((N_DST, f), f32)]),
        "gather_agg_bwd_dw": (gather_agg_bwd_dw_pallas,
                              [((N_SRC, f), f32), ((N_DST, R), i32),
                               ((N_DST, f), f32)]),
        "gather_cached_fwd": (gather_cached_fwd_pallas,
                              [((N_CACHE, f), f32), ((N_FEATS, f), f32),
                               ((N_SRC,), i32)]),
    }


@pytest.mark.parametrize("f", [47, 64, 128, 256, 602])
@pytest.mark.parametrize("kernel", ["gather_agg_fwd", "gather_agg_bwd_dx",
                                    "gather_agg_bwd_dw",
                                    "gather_cached_fwd"])
def test_kernel_compiles_for_v5e(one_chip, kernel, f):
    fn, shapes = _kernels(f)[kernel]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
