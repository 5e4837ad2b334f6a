"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).

Axes (runtime/sharding.py maps logical names onto them):
  pod    — data parallelism across pods (multi-pod only)
  data   — data parallelism / FSDP
  pipe   — pipeline-parallel stage axis (OMITTED when pipe == 1 so
           single-stage meshes are byte-identical to the pre-pipeline
           ones: no HLO diff, planner/schedule degrade exactly)
  model  — tensor/expert parallelism (the MoE all-to-all wire axis)

Each constructor also registers the machine's node topology (devices per
node along the minor/`model` axis) with ``repro.comm.topology`` so the
collective planner can factor the MoE all-to-all into intra-/inter-node
hops without re-deriving the machine shape at trace time."""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

from repro.comm.topology import register_node_size

# v5e: 4 chips share a host (the fast intra-node domain the 2-hop a2a
# exploits); override per-model via CommConfig.node_size / $REPRO_NODE_SIZE.
V5E_CHIPS_PER_HOST = 4


def _mesh_dims(data: int, pipe: int, model: int):
    """(shape, axes) with the pipe axis omitted at pipe == 1."""
    pipe = max(1, int(pipe))
    if pipe > 1:
        return (data, pipe, model), ("data", "pipe", "model")
    return (data, model), ("data", "model")


def make_production_mesh(*, multi_pod: bool = False, pipe: int = 1,
                         node_size: int = V5E_CHIPS_PER_HOST) -> Mesh:
    """Single pod: 16×16 = 256 chips (data, model).
    Multi-pod: 2×16×16 = 512 chips (pod, data, model).
    ``pipe`` > 1 carves the stage axis out of the data dimension:
    (16/pipe, pipe, 16) — the chip count is unchanged, stages ride the
    slower inter-host links while the a2a keeps the minor axis."""
    pipe = max(1, int(pipe))
    if 16 % pipe:
        raise ValueError(f"pipe={pipe} must divide the data dimension (16)")
    shape, axes = _mesh_dims(16 // pipe, pipe, 16)
    if multi_pod:
        shape, axes = (2,) + shape, ("pod",) + axes
    n = int(np.prod(shape))
    if len(jax.devices()) == n:
        # let make_mesh pick the device order for the topology
        mesh = jax.make_mesh(shape, axes,
                             axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
    else:
        # fewer/more devices than the full mesh: a row-major prefix (the
        # dry-run path)
        devs = np.array(jax.devices()[:n]).reshape(shape)
        mesh = Mesh(devs, axes)
    register_node_size(mesh, node_size)
    return mesh


def make_host_mesh(data: int = 1, pipe: int = 1, model: int = 1, *,
                   node_size: int = 0) -> Mesh:
    """Small mesh over however many (host) devices exist — the single
    host-mesh constructor for tests/examples.  ``node_size`` simulates a
    node boundary along the model axis for the hierarchical-a2a paths
    (0 = single-node: everything stays flat)."""
    shape, axes = _mesh_dims(data, pipe, model)
    n = int(np.prod(shape))
    devs = np.array(jax.devices()[:n]).reshape(shape)
    mesh = Mesh(devs, axes)
    register_node_size(mesh, node_size)
    return mesh
