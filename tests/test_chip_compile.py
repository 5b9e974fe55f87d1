"""The device path's Pallas kernels compile for a described TPU v5e.

Interpret mode (tests/test_kernels.py) checks results, not what the
chip's compiler accepts: block tiling, the primitives Mosaic lowers and
the dtypes v5e can load.  These tests lower each kernel at the paper's
width (D=1152) for one chip of a described ``v5e:2x2`` topology and
compile it with the installed TPU compiler; no chip is needed.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.distance_topk.distance_topk import distance_topk_pallas
from repro.kernels.distance_topk.grouped import _grouped_call

D = 1152  # configs/ecpfs_paper.py


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # other test workers may load the TPU compiler at the same time
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "qformat,k,metric",
    [
        ("int8", 32, "l2"),
        ("int8", 128, "cosine"),
        ("int8", 224, "ip"),
        ("float16", 128, "l2"),
    ],
)
def test_grouped_kernel_compiles_for_v5e(one_chip, qformat, k, metric):
    # one paper-size leaf (cap 455 -> 512 rows) per group, G=8 groups
    G, N = 8, 512
    args = (
        _spec((G, 1, D), jnp.float32, one_chip),
        _spec((G, N, D), jnp.dtype(qformat), one_chip),
        _spec((G, 1, 2), jnp.float32, one_chip),
        _spec((G, 1, 1), jnp.int32, one_chip),
    )
    lowered = _grouped_call.lower(
        *args, k=k, metric=metric, qformat=qformat, bn=128, interpret=False
    )
    _assert_kernel(lowered.compile())


def test_distance_topk_compiles_for_v5e(one_chip):
    q = _spec((128, D), jnp.float32, one_chip)
    c = _spec((4096, D), jnp.float32, one_chip)
    lowered = distance_topk_pallas.lower(q, c, k=32, metric="l2")
    _assert_kernel(lowered.compile())
