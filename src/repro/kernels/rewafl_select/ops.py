"""Backend dispatch for the fused utility→top-K→FedAvg hot path.

`FLConfig.kernel_backend` semantics, shared by every consumer
(`core/round.py` selection + `_fedavg`, `core/async_agg.land_once`):

  xla     the reference composition exactly as shipped before this
          module existed — materialise the (S,) utility, rank it, mask
          the dense reduction. Golden histories are bitwise on this path.
  pallas  the fused pass. Where Pallas can lower (TPU, or
          `interpret=True` in tests) the selection kernel runs with its
          VMEM candidate scratch; elsewhere the fused rank-space
          emission in `core.selection` serves the same masks from a
          single `lax.top_k` — either way no (S,) rank sort and no dense
          (S, P) masked reduction.
  auto    resolves to pallas on a TPU, else xla.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import selection as sel
from repro.core import utility as util
from repro.kernels.fedavg import ops as fedavg_ops
from repro.kernels.mesh import replicated
from repro.kernels.rewafl_select import ref
from repro.kernels.rewafl_select import rewafl_select as kernel

BACKENDS = ("xla", "pallas", "auto")
TILED_MIN_S = 100_000  # below this the flat single-tile variant wins


def resolve_backend(backend: str) -> str:
    """'auto' → 'pallas' iff the default backend is a TPU."""
    if backend not in BACKENDS:
        raise ValueError(
            f"kernel_backend must be one of {BACKENDS}, got {backend!r}")
    if backend != "auto":
        return backend
    return "pallas" if _kernel_lowerable() else "xla"


def _kernel_lowerable() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _run_kernel(ui: util.UtilityInputs, available: jax.Array,
                rnd: jax.Array, k_exploit: int, k_explore: int, *,
                T_round: float, alpha: float, beta: float,
                interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """Pad leaves to the tile grid and run the fused selection kernel
    (flat below TILED_MIN_S, tiled at/above it); replicated on every
    device when the fleet is sharded (`kernels.mesh.replicated`)."""
    S = available.shape[-1]
    bs = kernel.BLOCK_S if S >= TILED_MIN_S else _round_up(S, 128)
    pad = _round_up(S, bs) - S

    def p(x, v=0.0):
        return jnp.pad(x, (0, pad), constant_values=v) if pad else x

    def run(*leaves):
        return kernel.select_topk(
            *leaves, k_exploit=k_exploit, k_explore=k_explore,
            T_round=float(T_round), alpha=float(alpha),
            beta=float(beta), block_s=bs, interpret=interpret)

    return replicated(run)(
        p(ui.stat), p(ui.t, 1.0), p(ui.e, 1.0), p(ui.residual),
        p(ui.e0), p(available.astype(jnp.float32)), p(rnd))


def _mask_from_slots(idx: jax.Array, live: jax.Array,
                     S: int) -> jax.Array:
    # dead slots scatter to the OOB index S and are dropped
    return jnp.zeros((S,), bool).at[
        jnp.where(live > 0, idx, S)].set(True, mode="drop")


def select_mask(key: jax.Array, k: int, available: jax.Array, eps: float,
                *, scores: Optional[jax.Array] = None,
                ui: Optional[util.UtilityInputs] = None,
                T_round: float = 1.0, alpha: float = 1.0,
                beta: float = 1.0, backend: str = "auto",
                interpret: Optional[bool] = None) -> jax.Array:
    """Static-ε ε-greedy selection mask. Scored either by the REWAFL
    utility computed from `ui` leaves (rea path — kernel-fusable) or by
    precomputed `scores` (oort/autofl/random paths — already a single
    `lax.top_k`, so both backends share the reference emission)."""
    b = resolve_backend(backend)
    if ui is not None and b == "pallas" \
            and (bool(interpret) or _kernel_lowerable()):
        k_eff = min(k, available.shape[-1])
        if k_eff <= 0:
            return jnp.zeros(available.shape, bool)
        k_explore = sel._explore_slots(eps, k_eff)
        rnd = jax.random.uniform(key, available.shape)
        idx, live = _run_kernel(ui, available, rnd,
                                k_eff - k_explore, k_explore,
                                T_round=T_round, alpha=alpha, beta=beta,
                                interpret=bool(interpret))
        return _mask_from_slots(idx, live, available.shape[-1])
    # xla, and the CPU 'pallas' lowering: the static-k reference already
    # emits one lax.top_k per rank query — nothing left to fuse on CPU
    if ui is not None:
        return ref.select_ref(key, k, available, eps, ui,
                              T_round=T_round, alpha=alpha, beta=beta)
    return sel.epsilon_greedy(key, scores, k, available, eps)


def select_traced(key: jax.Array, scores: jax.Array, k: int,
                  available: jax.Array, eps: jax.Array, *,
                  backend: str = "auto") -> jax.Array:
    """Traced-ε selection (the compile-once grid path). The pallas
    lowering swaps the (S,) stable argsort rank for the fused
    `lax.top_k` candidate emission — identical masks (shared tie rule),
    O(S·K) instead of O(S log S), no rank array."""
    if resolve_backend(backend) == "xla":
        return sel.epsilon_greedy_traced(key, scores, k, available, eps)
    return sel.epsilon_greedy_traced_fused(key, scores, k, available,
                                           eps)


def select_aggregate(key: jax.Array, k: int, available: jax.Array,
                     eps: float, ui: util.UtilityInputs,
                     deltas: jax.Array, weights: jax.Array, *,
                     T_round: float, alpha: float, beta: float,
                     backend: str = "auto",
                     interpret: Optional[bool] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """The full fused pass: utility → ε-greedy top-K → weight-normalised
    FedAvg of the selected (S, P) delta rows. Returns (mask (S,) bool,
    aggregate (P,) f32). The fused backends gather only the K selected
    rows and reduce them with `kernels/fedavg` — K·P bytes of delta
    traffic instead of the reference's dense S·P masked reduction."""
    b = resolve_backend(backend)
    if b == "xla":
        return ref.select_aggregate_ref(key, k, available, eps, ui,
                                        deltas, weights, T_round=T_round,
                                        alpha=alpha, beta=beta)
    S = available.shape[-1]
    k_eff = min(k, S)
    if k_eff <= 0:
        return (jnp.zeros((S,), bool),
                jnp.zeros(deltas.shape[1:], jnp.float32))
    if bool(interpret) or _kernel_lowerable():
        k_explore = sel._explore_slots(eps, k_eff)
        rnd = jax.random.uniform(key, available.shape)
        idx, live = _run_kernel(ui, available, rnd,
                                k_eff - k_explore, k_explore,
                                T_round=T_round, alpha=alpha, beta=beta,
                                interpret=bool(interpret))
        mask = _mask_from_slots(idx, live, S)
    else:
        mask = ref.select_ref(key, k_eff, available, eps, ui,
                              T_round=T_round, alpha=alpha, beta=beta)
        idx = jnp.nonzero(mask, size=k_eff, fill_value=0)[0]
        live = jnp.arange(k_eff) < mask.sum()
    w = weights[idx].astype(jnp.float32) * (live > 0)
    wn = w / jnp.maximum(w.sum(), 1e-9)
    out = fedavg_ops.weighted_aggregate(
        deltas[idx].astype(jnp.float32), wn, interpret=interpret)
    return mask, out
