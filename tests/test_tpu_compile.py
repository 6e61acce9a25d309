"""Compile rehearsals of the Pallas kernels for a TPU v5e, without a chip.

Interpret mode checks what the kernels compute, not whether Mosaic
accepts them: vector shapes, bitwidths, VMEM and partitioning are only
refused when the kernel is compiled for a TPU. These tests compile each
kernel, and one fleet-sharded engine chunk, for a described `v5e:2x2`
topology (`jax.experimental.topologies`, no chip needed) and assert
that the compiled text holds a Mosaic kernel call (`tpu_custom_call`).
They skip where libtpu cannot describe the topology.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import METHODS, FLConfig, init_fleet_state
from repro.kernels.fedavg import ops as fedavg_ops
from repro.kernels.rewafl_select import ops as rsel_ops
from repro.kernels.rewafl_select import rewafl_select as rsel_kernel
from repro.launch import engine as eng
from repro.models.fl_models import make_fl_model
from repro.sim.devices import build_fleet
from repro.sim.dynamics import init_env_state

K = 20
# every leaf size of the paper-width `cnn@mnist` (small=False)
PAPER_CNN_LEAF_SIZES = (16, 144, 32, 4_608, 128, 200_704, 10, 1_280)


@pytest.fixture(scope="module")
def topo():
    """Four described v5e chips; the persistent compile cache is off
    while they compile (their executables belong to no real device)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it cannot describe v5e
            pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        # the cache decides once per process whether it is used:
        # reset it so the flag is read again
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            cc.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _on_one_chip(topo, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(
                                    topo.devices[0]))


@pytest.mark.parametrize("k_exploit,k_explore", [(20, 0), (16, 4)])
@pytest.mark.parametrize("S,block_s", [(1_024, 1_024), (5_120, 5_120),
                                       (1 << 20, rsel_kernel.BLOCK_S)],
                         ids=["flat-1k", "flat-5k", "tiled-1M"])
def test_select_kernel_compiles(topo, S, block_s, k_exploit, k_explore):
    leaves = [_on_one_chip(topo, (S,))] * 7

    def select(*xs):
        return rsel_kernel.select_topk(
            *xs, k_exploit=k_exploit, k_explore=k_explore, T_round=60.0,
            alpha=1.0, beta=1.0, block_s=block_s)

    assert "tpu_custom_call" in _compiled_text(select, *leaves)


@pytest.mark.parametrize("P", PAPER_CNN_LEAF_SIZES)
def test_fedavg_kernel_compiles(topo, monkeypatch, P):
    monkeypatch.setattr(fedavg_ops, "_use_pallas", lambda: True)

    def aggregate(stack, w):
        return fedavg_ops.weighted_aggregate(stack, w, backend="pallas")

    txt = _compiled_text(aggregate, _on_one_chip(topo, (K, P)),
                         _on_one_chip(topo, (K,)))
    assert "tpu_custom_call" in txt


def test_fleet_sharded_chunk_compiles(topo, monkeypatch):
    """One scan chunk on the kernel path with the fleet sharded over four
    chips: GSPMD cannot partition a Mosaic kernel, so both kernels must
    run inside a replicated `shard_map`."""
    monkeypatch.setattr(rsel_ops, "_kernel_lowerable", lambda: True)
    monkeypatch.setattr(fedavg_ops, "_use_pallas", lambda: True)
    S, per_client = 40, 8
    mesh = Mesh(np.array(topo.devices[:4]), ("fleet",),
                axis_types=(AxisType.Auto,))
    fleet_sh = NamedSharding(mesh, PartitionSpec("fleet"))
    rep_sh = NamedSharding(mesh, PartitionSpec())

    def place(tree, sh):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                           sharding=sh), tree)

    model = make_fl_model("cnn@mnist", small=True)
    cfg = FLConfig(n_select=4, kernel_backend="auto")
    fleet = build_fleet(S, seed=0)
    params = model.init(jax.random.PRNGKey(0))
    args = (place(params, rep_sh),
            place(init_fleet_state(fleet, H0=cfg.policy.H0), fleet_sh),
            place(init_env_state(fleet, None), fleet_sh),
            place(fleet, fleet_sh),
            place(jnp.zeros((S, per_client, 28, 28, 1)), fleet_sh),
            place(jnp.zeros((S, per_client), jnp.int32), fleet_sh),
            place(jax.random.PRNGKey(1), rep_sh),
            place(jnp.int32(0), rep_sh))
    chunk = eng.make_chunk_fn(model, cfg, METHODS["rewafl"], chunk_size=2,
                              donate=True)
    with jax.set_mesh(mesh):
        txt = chunk.lower(*args).compile().as_text()
    # one selection call + one FedAvg call per parameter leaf
    assert txt.count("tpu_custom_call") == 1 + len(jax.tree.leaves(params))
