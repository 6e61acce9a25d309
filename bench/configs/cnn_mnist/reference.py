"""Plain reference of the configuration's two-layer CNN, in jax.numpy.

conv 3×3 (SAME, stride 1) → ReLU → max-pool 2×2 → conv 3×3 → ReLU →
max-pool 2×2 → flatten → dense → ReLU → dense, and a per-sample
cross-entropy. Parameters are laid out as the program's model takes
them. Imports nothing of the program. `dtype` is the precision of
storage and arithmetic; the matmul precision is the caller's.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def init(key, cfg: dict) -> dict:
    """Fan-in normal weights and zero biases, from one key."""
    h, w, c = cfg["input_shape"]
    k, c1, c2, d = cfg["kernel"], cfg["c1"], cfg["c2"], cfg["d_fc"]
    flat = (h // 4) * (w // 4) * c2
    ks = jax.random.split(key, 4)
    return {
        "conv1": {"w": _normal(ks[0], (k, k, c, c1), k * k * c),
                  "b": jnp.zeros((c1,), jnp.float32)},
        "conv2": {"w": _normal(ks[1], (k, k, c1, c2), k * k * c1),
                  "b": jnp.zeros((c2,), jnp.float32)},
        "fc1": {"w": _normal(ks[2], (flat, d), flat),
                "b": jnp.zeros((d,), jnp.float32)},
        "fc2": {"w": _normal(ks[3], (d, cfg["n_classes"]), d),
                "b": jnp.zeros((cfg["n_classes"],), jnp.float32)},
    }


def _conv(p, x, dtype):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"].astype(dtype)


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def logits(params, x, dtype=jnp.float32):
    h = _pool(jax.nn.relu(_conv(params["conv1"], x.astype(dtype), dtype)))
    h = _pool(jax.nn.relu(_conv(params["conv2"], h, dtype)))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["fc1"]["w"].astype(dtype)
                    + params["fc1"]["b"].astype(dtype))
    return h @ params["fc2"]["w"].astype(dtype) + params["fc2"]["b"].astype(
        dtype)


def per_sample_loss(params, x, y, dtype=jnp.float32):
    """(B,) cross-entropy, in `dtype` throughout."""
    z = logits(params, x, dtype)
    return (jax.nn.logsumexp(z, axis=-1)
            - jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0])


def n_params(cfg: dict) -> int:
    h, w, c = cfg["input_shape"]
    k, c1, c2, d, n = (cfg["kernel"], cfg["c1"], cfg["c2"], cfg["d_fc"],
                       cfg["n_classes"])
    flat = (h // 4) * (w // 4) * c2
    return (k * k * c * c1 + c1 + k * k * c1 * c2 + c2 + flat * d + d
            + d * n + n)


def _layer_flops(cfg: dict):
    """Multiply-add FLOPs (2 per MAC) of each layer, per sample."""
    h, w, c = cfg["input_shape"]
    k, c1, c2, d, n = (cfg["kernel"], cfg["c1"], cfg["c2"], cfg["d_fc"],
                       cfg["n_classes"])
    return (2 * h * w * k * k * c * c1,
            2 * (h // 2) * (w // 2) * k * k * c1 * c2,
            2 * (h // 4) * (w // 4) * c2 * d,
            2 * d * n)


def forward_flops(cfg: dict) -> int:
    """Per-sample FLOPs of one forward pass (convolutions and matmuls)."""
    return sum(_layer_flops(cfg))


def train_flops(cfg: dict) -> int:
    """Per-sample FLOPs of one forward and backward pass that training
    needs: the forward, every layer's weight gradient, and every input
    gradient but the first layer's, which nothing consumes."""
    layers = _layer_flops(cfg)
    return 3 * sum(layers) - layers[0]
