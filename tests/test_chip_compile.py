"""The codec kernels compile for a described TPU v5e at real grid widths.

Interpret-mode tests cannot see what Mosaic refuses: block shapes off the
(8, 128) tiling and blocks that overrun scoped VMEM.  These cases compile
``grib_pack`` and ``grib_unpack`` with ``interpret=False`` for one chip of
a described ``v5e:2x2`` and check that the program holds the Mosaic kernel.
Nothing runs; no chip is needed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports this
file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.grib_pack import grib_pack, grib_unpack

#: 0.25-degree and 0.1-degree regular lat-lon at one output step of 32
#: fields, and the hammer's (20, 2048, 128) batch
GLOBAL_SHAPES = [(32, 721, 1440), (32, 1801, 3600)]
HAMMER_SHAPE = (20, 2048, 128)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("nbits", [8, 16, 24])
@pytest.mark.parametrize("shape", GLOBAL_SHAPES + [HAMMER_SHAPE])
def test_pack_compiles_for_v5e(one_chip, shape, nbits):
    x = _spec(shape, jnp.float32, one_chip)
    compiled = grib_pack.lower(x, nbits=nbits, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", GLOBAL_SHAPES + [HAMMER_SHAPE, (1, 1801, 3600)])
def test_unpack_compiles_for_v5e(one_chip, shape):
    vec = _spec(shape[:1], jnp.float32, one_chip)
    codes = _spec(shape, jnp.int32, one_chip)
    compiled = grib_unpack.lower(codes, vec, vec, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
