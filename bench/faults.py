"""Faults planted under the timed path: each is a program that a sound
comparison must refuse. The benchmark's tests plant them at CPU size;
`control.py --fault` reads them at a cell's own size on the chip.

    state_unchanged     the aggregation hands back the round's model
    half_cohort         half of the cohort left out of FedAvg, the mean
                        taken over the rest
    selection_altered   one selected device swapped for an unselected one
                        where the selection is produced
"""
import contextlib

import jax.numpy as jnp

from repro.core import round as round_mod
from repro.kernels.rewafl_select import ops as rsel_ops

FAULTS = ("state_unchanged", "half_cohort", "selection_altered")


@contextlib.contextmanager
def planted(fault: str):
    fedavg, select = round_mod._fedavg, rsel_ops.select_mask
    if fault == "state_unchanged":
        def patched_fedavg(g, c, w, backend=None):
            return g
        round_mod._fedavg = patched_fedavg
    elif fault == "half_cohort":
        def patched_fedavg(g, c, w, backend=None):
            k = w.shape[0]
            return fedavg(g, c, w * (jnp.arange(k) < k // 2), backend)
        round_mod._fedavg = patched_fedavg
    elif fault == "selection_altered":
        def patched_select(key, k, available, eps, **kw):
            m = select(key, k, available, eps, **kw)
            i = jnp.argmax(m)
            j = jnp.argmax(~m & available)
            return m.at[i].set(False).at[j].set(True)
        rsel_ops.select_mask = patched_select
    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    try:
        yield
    finally:
        round_mod._fedavg, rsel_ops.select_mask = fedavg, select
