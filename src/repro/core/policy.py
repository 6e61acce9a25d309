"""REWA local computing policy — Eqns (3)–(4) — and its baselines.

Eqn (3): H(i,r) = ⌈H(i, r−u−1) + ψ(s(i,r))·ΔH⌉ when selected (V=1);
          unchanged otherwise. ψ(·) ≥ 0 and decreasing in the uplink rate.

Eqn (4): ε_i^r = |Loss(θ_i^{last}) − Loss(θ^{r−1})| · (E_i^{last} − E0)
                 / e_cp(i, last); stop growing H when ε < ε_th.

AdaH (REAFL+LUPA baseline, [23]): H(r) = ⌈H0 + Σ_{l≤r} ψ·ΔH⌉ — grows
every round for every device, selection-independent, no stopping.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PolicyCfg:
    H0: int = 5
    H_max: int = 30            # upper clip of the adaptive H (Eqn (3))
    dH: float = 2.0            # ΔH increment unit
    psi0: float = 1.0          # ψ scale
    s_ref: float = 20e6        # bps — rate normalisation in ψ
    psi_fixed: float = 0.3     # AdaH's constant ψ
    eps_th: float = 4.0        # ε threshold of Eqn (4) — scaled to the
                               # simulator's (E−E0)/e_cp ≈ 20–40 regime


def psi(rates: jax.Array, cfg: PolicyCfg) -> jax.Array:
    """Non-negative, decreasing in the transmission rate: fast uplinks get
    small H increments (their comm latency/energy is already low)."""
    return cfg.psi0 * cfg.s_ref / (cfg.s_ref + jnp.maximum(rates, 0.0))


def stopping_eps(last_local_loss: jax.Array, global_loss: jax.Array,
                 last_energy: jax.Array, e0: jax.Array,
                 last_ecp: jax.Array) -> jax.Array:
    """Eqn (4)."""
    return (jnp.abs(last_local_loss - global_loss)
            * jnp.maximum(last_energy - e0, 0.0)
            / jnp.maximum(last_ecp, 1e-9))


def h_rewa(H: jax.Array, rates: jax.Array, eps: jax.Array,
           cfg: PolicyCfg) -> jax.Array:
    """Candidate H for this round under REWA (applied if selected):
    grow by ψ(s)·ΔH unless the energy-utility stopping criterion fires."""
    grown = jnp.ceil(H.astype(jnp.float32) + psi(rates, cfg) * cfg.dH)
    keep_growing = eps >= cfg.eps_th
    out = jnp.where(keep_growing, grown, H.astype(jnp.float32))
    return jnp.clip(out, 1, cfg.H_max).astype(jnp.int32)


def h_adah(round_idx: jax.Array, S: int, cfg: PolicyCfg) -> jax.Array:
    """AdaH [23]: selection-independent global schedule."""
    h = jnp.ceil(cfg.H0 + (round_idx.astype(jnp.float32) + 1.0)
                 * cfg.psi_fixed * cfg.dH)
    return jnp.full((S,), 1, jnp.int32) * jnp.clip(h, 1, cfg.H_max).astype(jnp.int32)


def h_fixed(S: int, cfg: PolicyCfg) -> jax.Array:
    return jnp.full((S,), cfg.H0, jnp.int32)
