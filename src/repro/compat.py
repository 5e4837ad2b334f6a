"""The two JAX 0.9 entry points every mesh-aware call site goes through.

  shard_map   ``jax.shard_map`` with the replication check off by default:
              every region here returns per-shard values whose replication
              the checker cannot always prove (explicit collectives).
  set_mesh    ``jax.set_mesh``, the ambient-mesh context manager.
"""
from __future__ import annotations

from typing import Callable

import jax


def shard_map(f: Callable, mesh, in_specs, out_specs,
              check_replication: bool = False) -> Callable:
    """``jax.shard_map`` with ``check_vma=check_replication``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_replication)


set_mesh = jax.set_mesh

__all__ = ["shard_map", "set_mesh"]
