"""Placing the Pallas kernels on a sharded program.

GSPMD cannot partition a Mosaic kernel. When the engine shards the fleet
axis it traces its chunks under `jax.set_mesh(mesh)`; each kernel call
then runs inside a `shard_map` whose operands are replicated, so every
device runs the whole (small) kernel on the same inputs. The selection
kernel's seven (S,) leaves and the FedAvg kernel's (K, P) cohort stack
are small enough to gather; per-shard candidates with a merge would
avoid it.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec


def replicated(fn):
    """`fn` unchanged outside a mesh context; inside one, `fn` wrapped in
    a `shard_map` with replicated operands and outputs."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), check_vma=False)
