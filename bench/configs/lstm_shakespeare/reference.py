"""Plain reference of the configuration's character LSTM, in jax.numpy.

Embedding → one LSTM layer (gates i, f, g, o from x·W + h·R + b, forget
bias +1) → dense head over the alphabet; the loss of a sequence is the
mean next-character cross-entropy over its first T−1 positions.
Parameters are laid out as the program's model takes them. Imports
nothing of the program. `dtype` is the precision of storage and
arithmetic; the matmul precision is the caller's.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def init(key, cfg: dict) -> dict:
    """Embedding N(0, 0.1²), fan-in normal matrices, zero biases."""
    v, e, h = cfg["vocab"], cfg["d_embed"], cfg["d_hidden"]
    ks = jax.random.split(key, 4)
    nrm = jax.random.normal
    return {
        "embed": {"table": nrm(ks[0], (v, e), jnp.float32) * 0.1},
        "lstm": {"w": nrm(ks[1], (e, 4 * h), jnp.float32) / math.sqrt(e),
                 "r": nrm(ks[2], (h, 4 * h), jnp.float32) / math.sqrt(h),
                 "b": jnp.zeros((4 * h,), jnp.float32)},
        "head": {"w": nrm(ks[3], (h, v), jnp.float32) / math.sqrt(h),
                 "b": jnp.zeros((v,), jnp.float32)},
    }


def logits(params, ids, dtype=jnp.float32):
    """ids (B, T) → logits (B, T, V)."""
    lp = {k: v.astype(dtype) for k, v in params["lstm"].items()}
    x = params["embed"]["table"].astype(dtype)[ids]
    B, dh = ids.shape[0], lp["r"].shape[0]

    def cell(carry, x_t):
        h, c = carry
        pre = x_t @ lp["w"] + h @ lp["r"] + lp["b"]
        i, f, g, o = jnp.split(pre, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    zero = jnp.zeros((B, dh), dtype)
    _, hs = jax.lax.scan(cell, (zero, zero), x.swapaxes(0, 1))
    return (hs.swapaxes(0, 1) @ params["head"]["w"].astype(dtype)
            + params["head"]["b"].astype(dtype))


def per_sample_loss(params, x, y, dtype=jnp.float32):
    """(B,) mean next-character cross-entropy; `y` is unused."""
    z = logits(params, x[:, :-1], dtype)
    nll = (jax.nn.logsumexp(z, axis=-1)
           - jnp.take_along_axis(z, x[:, 1:, None], axis=-1)[..., 0])
    return jnp.mean(nll, axis=-1)


def n_params(cfg: dict) -> int:
    v, e, h = cfg["vocab"], cfg["d_embed"], cfg["d_hidden"]
    return v * e + e * 4 * h + h * 4 * h + 4 * h + h * v + v


def forward_flops(cfg: dict) -> int:
    """Per-sequence FLOPs of one forward pass: T−1 steps of the input
    and recurrent gate matmuls and the head (the lookup is free)."""
    v, e, h = cfg["vocab"], cfg["d_embed"], cfg["d_hidden"]
    return (cfg["seq_len"] - 1) * 2 * (e * 4 * h + h * 4 * h + h * v)


def train_flops(cfg: dict) -> int:
    """Forward, weight gradients and input gradients of every matmul (the
    input projection's feeds the embedding's gradient)."""
    return 3 * forward_flops(cfg)
