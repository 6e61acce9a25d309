"""Jit'd public op: shape-generic weighted aggregation with backend dispatch.

TPU backends run the Pallas kernel (VMEM-tiled); CPU uses the pure-jnp
oracle — identical math, verified by tests/test_kernels_fedavg.py in
interpret mode across shape/dtype sweeps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fedavg import fedavg as kernel
from repro.kernels.fedavg import ref
from repro.kernels.mesh import replicated


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def weighted_aggregate(stack: jax.Array, weights: jax.Array,
                       *, interpret: bool | None = None,
                       backend: str | None = None) -> jax.Array:
    """out = Σ_k w_k·stack[k] for stack (K, ...) of any shape/dtype.

    `backend` pins the lowering (`FLConfig.kernel_backend`, resolved):
    'xla' forces the pure-jnp reference (the golden bitwise path),
    'pallas' runs the kernel where it can lower (TPU, or interpret=True
    in tests) and falls back to the reference elsewhere so CPU tier-1
    stays green. None keeps the legacy attached-backend heuristic."""
    if backend == "xla":
        return ref.weighted_aggregate(stack, weights)
    if backend == "pallas":
        if not (bool(interpret) or _use_pallas()):
            return ref.weighted_aggregate(stack, weights)
    elif interpret is None and not _use_pallas():
        return ref.weighted_aggregate(stack, weights)
    K = stack.shape[0]
    flat = stack.reshape(K, -1)
    P = flat.shape[1]
    bp = min(kernel.BLOCK_P, _round_up(P, 128))
    pad = (-P) % bp
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    out = replicated(functools.partial(
        kernel.weighted_aggregate_flat, interpret=bool(interpret),
        block_p=bp))(flat, weights)
    if pad:
        out = out[:P]
    return out.reshape(stack.shape[1:]).astype(stack.dtype)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
