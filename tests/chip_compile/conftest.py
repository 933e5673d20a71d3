"""The described ``v5e:2x2`` and its meshes, once a test module (the rules:
this package's docstring)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip, not fail
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh1(topo):
    from cylon_tpu.ctx.context import ROW_AXIS
    return Mesh(np.array(topo.devices[:1]), (ROW_AXIS,))


@pytest.fixture(scope="module")
def mesh4(topo):
    """The four described chips of the v5e:2x2 host, as the engine's mesh."""
    from cylon_tpu.ctx.context import ROW_AXIS
    return Mesh(np.array(topo.devices[:4]), (ROW_AXIS,))


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()
