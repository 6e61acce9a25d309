"""The simulated phone fleet, drawn from a seed on the host.

A copy of the fleet law of the program's `sim/devices.build_fleet`, kept
here so that the benchmark hands the program arrays it made itself: five
phone and laptop types in equal shares (a remainder round-robins over
them), initial battery a clipped normal share of capacity, half of the
devices in a poor radio environment, Poisson local data sizes.

A traffic file may replace the table's time per local iteration, set at
the paper's task scale, by `t_iter_s`: one measured time per device
type, in the order of `DEVICE_TYPES`, given only with `t_iter_source`,
where those times were measured.
"""
from __future__ import annotations

import numpy as np

# (t_iter s, p_compute W, p_tx W, battery J, rate_high bps, rate_low bps)
DEVICE_TYPES = (
    ("xiaomi_12s", 1.0, 6.5, 2.5, 62e3, 79.60e6, 0.64e6),
    ("honor_70", 1.8, 5.5, 2.5, 69e3, 45.0e6, 0.64e6),
    ("honor_play_6t", 3.5, 4.5, 2.5, 69e3, 12.0e6, 0.64e6),
    ("teclast_m40", 3.0, 5.0, 1.8, 97e3, 40.0e6, 2.0e6),
    ("macbook_pro_2018", 0.6, 22.0, 1.2, 208.8e3, 60.0e6, 4.0e6),
)

FIELDS = ("type_id", "t_iter", "p_compute", "p_tx", "battery_j",
          "init_energy", "rate_mean", "rate_sigma", "rate_high", "rate_low",
          "e0_reserve", "data_size")


def draw_fleet(n: int, seed: int, *, frac_low_rate: float = 0.5,
               e0_frac: float = 0.05, init_energy_mean: float = 0.5,
               init_energy_std: float = 0.25, data_size: int = 500,
               rate_sigma: float = 0.3, t_iter_s=None,
               t_iter_source: str = "") -> dict:
    """{field: (n,) array} in the order and dtypes of `FIELDS`."""
    if (t_iter_s is None) != (not t_iter_source):
        raise ValueError("t_iter_s and t_iter_source go together")
    rng = np.random.RandomState(seed)
    nt = len(DEVICE_TYPES)
    per, rem = divmod(n, nt)
    type_id = np.concatenate([np.repeat(np.arange(nt), per),
                              np.arange(rem)]).astype(np.int32)
    table = np.asarray([t[1:] for t in DEVICE_TYPES], np.float64)
    if t_iter_s is not None:
        if len(t_iter_s) != nt:
            raise ValueError(f"t_iter_s needs {nt} times, one a device type")
        table[:, 0] = t_iter_s
    t_iter, p_comp, p_tx, battery, r_hi, r_lo = (
        table[type_id, j].astype(np.float32) for j in range(6))
    init_frac = np.clip(rng.normal(init_energy_mean, init_energy_std, n),
                        0.10, 1.0)
    low = rng.rand(n) < frac_low_rate
    sizes = np.maximum(1, rng.poisson(data_size, n)).astype(np.int32)
    return {
        "type_id": type_id,
        "t_iter": t_iter,
        "p_compute": p_comp,
        "p_tx": p_tx,
        "battery_j": battery,
        "init_energy": (battery * init_frac).astype(np.float32),
        "rate_mean": np.where(low, r_lo, r_hi).astype(np.float32),
        "rate_sigma": np.full((n,), rate_sigma, np.float32),
        "rate_high": r_hi,
        "rate_low": r_lo,
        "e0_reserve": (battery * e0_frac).astype(np.float32),
        "data_size": sizes,
    }
