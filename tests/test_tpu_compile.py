"""The bitmm kernels compile for a TPU v5e chip that is described, not attached.

The TPU compiler refuses what the Pallas interpreter accepts (blocks off the
(8, 128) tiling, cross-lane reshapes, too much VMEM), so these tests lower
both kernels with ``interpret=False`` for one chip of a described ``v5e:2x2``
at G5K's padded size and at the PBME gate ``max_bitmatrix_n``.  The topology
is described inside a fixture only: describing it loads the TPU library,
which one process at a time may hold.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core.engine import EngineConfig
from repro.kernels import bitmm

SIZES = [5120, EngineConfig().max_bitmatrix_n]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep such entries out of it."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _hlo(fn, n_args, n, sharding):
    words = jax.ShapeDtypeStruct((n, n // 32), jnp.uint32, sharding=sharding)
    compiled = (
        jax.jit(lambda *a: fn(*a, interpret=False))
        .lower(*[words] * n_args)
        .compile()
    )
    return compiled.as_text()


@pytest.mark.parametrize("n", SIZES)
def test_bitmm_compiles_for_v5e(n, one_chip, no_persistent_cache):
    assert "tpu_custom_call" in _hlo(bitmm.bitmm_call, 2, n, one_chip)


@pytest.mark.parametrize("n", SIZES)
def test_bitmm_fused_delta_compiles_for_v5e(n, one_chip, no_persistent_cache):
    assert "tpu_custom_call" in _hlo(bitmm.bitmm_fused_delta_call, 3, n, one_chip)
