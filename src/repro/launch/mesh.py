"""Production mesh builders.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax

from repro.nn.sharding import ShardCfg


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds a leading pod axis (2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_shard_cfg(*, multi_pod: bool = False) -> ShardCfg:
    mesh = make_production_mesh(multi_pod=multi_pod)
    data_axes = ("pod", "data") if multi_pod else ("data",)
    return ShardCfg(mesh=mesh, data_axes=data_axes, model_axis="model")


def make_host_mesh(shape=(2, 2), axes=("data", "model")) -> ShardCfg:
    """Small mesh for CPU tests (requires enough host devices)."""
    mesh = jax.make_mesh(shape, axes)
    return ShardCfg(mesh=mesh, data_axes=axes[:-1], model_axis=axes[-1])


def make_fleet_mesh(n_shards=None):
    """1-D mesh over the FL fleet axis S (axis name "fleet") — the engine
    shards every (S, ...) array over it; selection top-k and the K-slot
    gathers stay global ops partitioned by GSPMD. The axis is `Auto`:
    with `Explicit` (jax.make_mesh's default) every gather over the
    sharded axis would need a hand-written output sharding."""
    n = n_shards or len(jax.devices())
    return jax.make_mesh((n,), ("fleet",),
                         axis_types=(jax.sharding.AxisType.Auto,))
