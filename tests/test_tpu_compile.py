"""The device programs of the delivery path compile for a TPU v5e.

The TPU compiler compiles for a chip that is described, not attached, so
these run on a CPU-only host: the ARIMA bank at every history bucket and
the placement's Lloyd iterations, at the shapes the replay uses.  Nothing
runs; a pass says only that the chip's compiler accepts the programs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import arima

# by module name: ``repro.core`` re-exports a function called ``kmeans``
kmeans = importlib.import_module("repro.core.kmeans")

# placement clusters the last <= 5,000 requests on 3 features into <= 4
# groups with 25 Lloyd iterations (placement.py, simulator.py)
LLOYD_SHAPE = (5000, 3, 4, 25)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler would otherwise log under /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler or topology on this host
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's executables are written to the persistent
        # cache but cannot be read back without one: keep the cache out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("n", [*arima._BUCKETS, arima.ARIMA().n])
def test_bank_compiles_for_v5e(one_chip, n):
    bank = arima._compiled_bank(n, 2, 1, 1, 200, 0.05)
    rows = jax.ShapeDtypeStruct((arima.BANK_WIDTH, n), jnp.float32,
                                sharding=one_chip)
    compiled = bank.lower(rows).compile()
    out = compiled.out_info
    assert (out.shape, out.dtype) == ((arima.BANK_WIDTH,), jnp.float32)


def test_lloyd_compiles_for_v5e(one_chip):
    n, dim, k, iters = LLOYD_SHAPE
    lloyd = kmeans._compiled_lloyd(n, dim, k, iters)
    x = jax.ShapeDtypeStruct((n, dim), jnp.float32, sharding=one_chip)
    centers0 = jax.ShapeDtypeStruct((k, dim), jnp.float32, sharding=one_chip)
    compiled = lloyd.lower(x, centers0).compile()
    centers, assign, inertia = compiled.out_info
    assert centers.shape == (k, dim) and assign.shape == (n,)
    assert inertia.shape == ()
