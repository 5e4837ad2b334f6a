"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests run on the 1 real CPU
device (the 512-device override belongs to launch/dryrun.py only)."""
import pytest

import jax


@pytest.fixture(scope="session")
def mesh():
    """1x1 (data, model) mesh over the single CPU device: exercises every
    mesh-aware code path (shard_map, collectives degenerate to identity)."""
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(1, 1, 1)


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)
