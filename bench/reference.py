"""Plain reference of one REWAFL round, for the `correct` comparison.

Given the carry the program's campaign held at the start of a round
(global model, fleet state, round key), it computes that round again as
the paper states it, imports nothing of the program and takes no table
or weight it made besides that carry:

* the probe: every device's mean loss of the global model over its
  first `probe_size` samples;
* the local-iteration policy, Eqns (3)–(4): H grows by ⌈ψ(rate)·ΔH⌉
  unless the energy-utility stopping value ε falls under its threshold;
* the round's latency and energy estimates, the utility of Eqn (2) and
  the top-K selection (ties to the lower device index);
* local SGD of each selected, feasible device (H iterations of a
  random minibatch) and the data-size-weighted FedAvg of their models.

`follow_rounds` trains the model through several rounds from a carry,
with each round's selection and local-iteration counts taken as the
program decided them, for the comparison of the aggregated model.

A configuration may declare `trainable`: the top-level keys of the
parameter tree that devices train and upload. The other leaves are
frozen: local SGD closes over them, unbatched, and FedAvg hands them
back untouched. Absent, the whole tree is trainable.

The probe runs PROBE_BLOCK devices (where that divides S) to a call of
the configuration's `per_sample_loss`, and local SGD all K slots in one
vmap. A configuration whose activations would not fit at that many
samples bounds them inside its own `per_sample_loss` (a `lax.map` over
groups of samples, under `jax.checkpoint` for the gradient).

Random draws follow the campaign's key exactly as the round states them
(one split per round, then rates / selection / training keys, one key
per training slot), so a device trains on the same minibatches here.

`precision="reference"` computes the model in the precision the
configuration states: float32 storage, activations and losses, at the
TPU's default matmul precision (bfloat16 operands, float32
accumulation). `precision="control"` computes it in bfloat16 (weights,
activations, losses, gradients and the stored model), the precision
below, which the comparison has to reject.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

PROBE_BLOCK = 250     # devices per block of the probe, to bound memory


class RoundOut(NamedTuple):
    probe_before: float      # fleet-mean probe loss of the round's model
    probe_after: float       # the same of the aggregated model
    change: float            # mean over devices of |their probe's change|
    selected: np.ndarray     # (S,) bool
    new_H: np.ndarray        # (S,) int32
    utility: np.ndarray      # (S,) f32, -inf where unavailable
    eps: np.ndarray          # (S,) f32 stopping value of Eqn (4)


def _dtype(precision: str):
    return jnp.bfloat16 if precision == "control" else jnp.float32


MATMUL = "default"    # the precision the configurations state


def split(params: dict, trainable):
    """(frozen, trainable) subtrees of `params` by its top-level keys;
    with `trainable` None every leaf is trainable."""
    if trainable is None:
        return {}, params
    missing = set(trainable) - set(params)
    if missing:
        raise KeyError(f"trainable keys {sorted(missing)} are not in the "
                       f"parameter tree's {sorted(params)}")
    return ({k: v for k, v in params.items() if k not in trainable},
            {k: v for k, v in params.items() if k in trainable})


def merge(frozen: dict, trainable: dict) -> dict:
    """The whole tree again from `split`'s two parts."""
    return {**frozen, **trainable}


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6))
def probe_losses(model, params, cx, cy, probe: int, dtype,
                 matmul: str) -> jax.Array:
    """(S,) mean per-sample loss over each device's first `probe`
    samples, in blocks of PROBE_BLOCK devices."""
    S = cx.shape[0]
    blk = PROBE_BLOCK if S % PROBE_BLOCK == 0 else S
    px = cx[:, :probe].reshape((S // blk, blk * probe) + cx.shape[2:])
    py = cy[:, :probe].reshape(S // blk, blk * probe)
    p = jax.tree.map(lambda a: a.astype(dtype), params)

    def block(b):
        with jax.default_matmul_precision(matmul):
            ls = model.per_sample_loss(p, b[0], b[1], dtype)
        return jnp.mean(ls.astype(jnp.float32).reshape(blk, probe), axis=1)

    return jax.lax.map(block, (px, py)).reshape(S)


@functools.partial(jax.jit, static_argnums=(0, 6, 7, 8, 9, 10))
def _local_sgd(model, frozen, params, xk, yk, hk_keys, H_max, batch, lr,
               dtype, matmul):
    """Per-slot SGD of the trainable `params` from the common model, the
    `frozen` leaves held: H_k live iterations each."""
    Hk, keys = hk_keys
    n = xk.shape[1]
    base = jax.tree.map(lambda a: a.astype(dtype), frozen)

    def loss(p, x, y):
        with jax.default_matmul_precision(matmul):
            return jnp.mean(model.per_sample_loss(merge(base, p), x, y,
                                                  dtype))

    def one(x, y, H, key):
        p0 = jax.tree.map(lambda a: a.astype(dtype), params)

        def body(it, p):
            idx = jax.random.randint(jax.random.fold_in(key, it), (batch,),
                                     0, n)
            g = jax.grad(loss)(p, x[idx], y[idx])
            stepped = jax.tree.map(lambda a, b: (a - lr * b).astype(dtype),
                                   p, g)
            return jax.tree.map(lambda s, a: jnp.where(it < H, s, a),
                                stepped, p)

        return jax.lax.fori_loop(0, H_max, body, p0)

    return jax.vmap(one)(xk, yk, Hk, keys)


def _top_k(utility: np.ndarray, k: int) -> np.ndarray:
    """Mask of the k largest finite utilities, ties to the lower index."""
    order = np.argsort(-utility, kind="stable")
    live = order[np.isfinite(utility[order])][:k]
    mask = np.zeros(utility.shape, bool)
    mask[live] = True
    return mask


def run_round(model, mcfg: dict, fl: dict, fleet: Dict[str, np.ndarray],
              cx, cy, params, state: Dict[str, np.ndarray], key,
              K: int, precision: str = "reference") -> RoundOut:
    """One round from the carry (`params`, `state`, round `key`)."""
    dtype, matmul = _dtype(precision), MATMUL
    S = fleet["type_id"].shape[0]
    probe = int(fl["probe_size"])
    g_dev = probe_losses(model, params, cx, cy, probe, dtype, matmul)
    g_dec = np.asarray(g_dev, np.float32)
    g_val = np.asarray(g_dev, np.float64)

    _, kr = jax.random.split(jnp.asarray(key))
    k_rate, _, k_train = jax.random.split(kr, 3)
    f32 = np.float32
    sigma = fleet["rate_sigma"]
    fading = np.exp(sigma * np.asarray(jax.random.normal(k_rate, (S,)),
                                       f32) - f32(0.5) * sigma ** 2)
    rates = (fleet["rate_mean"] * fading).astype(f32)

    # Eqn (4) and Eqn (3)
    eps = (np.abs(state["last_local_loss"] - g_dec)
           * np.maximum(state["last_energy"] - fleet["e0_reserve"], f32(0))
           / np.maximum(state["last_ecp"], f32(1e-9))).astype(f32)
    psi = f32(fl["psi0"]) * f32(fl["s_ref"]) / (
        f32(fl["s_ref"]) + np.maximum(rates, f32(0)))
    H = state["H"]
    grown = np.ceil(H.astype(f32) + psi * f32(fl["dH"]))
    H_cand = np.clip(np.where(eps >= f32(fl["eps_th"]), grown,
                              H.astype(f32)), 1, fl["H_max"]).astype(np.int32)

    # latency / energy estimates and Eqn (2); n_params counts what a
    # device uploads
    bits = f32(32 * model.n_params(mcfg))
    t_comp = H_cand.astype(f32) * fleet["t_iter"]
    t_comm = bits / np.maximum(rates, f32(1))
    t = t_comp + t_comm
    e = t_comp * fleet["p_compute"] + t_comm * fleet["p_tx"]
    T = f32(fl["T_round"])
    lat = np.where(t > T, (T / np.maximum(t, f32(1e-9)))
                   ** f32(fl["alpha"]), f32(1))
    head = state["residual_energy"] - fleet["e0_reserve"]
    eng = np.where(e < head, np.maximum(head / np.maximum(e, f32(1e-9)),
                                        f32(1e-9)) ** f32(fl["beta"]),
                   f32(0))
    util = (state["last_stat"] * lat * eng).astype(f32)
    util = np.where(state["dropped"], -np.inf, util).astype(f32)
    selected = _top_k(util, min(K, S))
    participating = selected & (e < head)
    new_params = train_and_aggregate(
        model, mcfg, fl, fleet, cx, cy, params, selected, participating,
        H_cand, k_train, K, dtype, matmul)
    g_after = np.asarray(probe_losses(model, new_params, cx, cy, probe,
                                      dtype, matmul), np.float64)
    new_H = np.where(participating, H_cand, H).astype(np.int32)
    return RoundOut(float(g_val.mean()), float(g_after.mean()),
                    float(np.abs(g_after - g_val).mean()), selected, new_H,
                    util, eps)


def train_and_aggregate(model, mcfg: dict, fl: dict,
                        fleet: Dict[str, np.ndarray], cx, cy, params,
                        selected: np.ndarray, participating: np.ndarray,
                        H: np.ndarray, k_train, K: int, dtype, matmul: str):
    """Local SGD of the K training slots (selected devices in index
    order, H[i] live iterations each, one key per slot) and the
    data-size-weighted FedAvg of the participants' trainable leaves; the
    frozen leaves come back untouched."""
    f32 = np.float32
    frozen, params = split(params, mcfg.get("trainable"))
    sel_idx = np.flatnonzero(selected)
    live = np.zeros(K, bool)
    live[:len(sel_idx)] = True
    sel_idx = np.concatenate([sel_idx, np.zeros(K - len(sel_idx), int)])
    part_k = participating[sel_idx] & live
    keys = jax.random.split(k_train, K)
    client = _local_sgd(model, frozen, params, cx[sel_idx], cy[sel_idx],
                        (jnp.asarray(H[sel_idx]), keys),
                        int(fl["H_max"]), int(fl["batch_size"]),
                        float(fl["lr"]), dtype, matmul)
    w = fleet["data_size"][sel_idx].astype(f32) * part_k.astype(f32)
    if w.sum() > 0:
        wn = jnp.asarray(w / max(w.sum(), f32(1e-9)))
        return merge(frozen, jax.tree.map(
            lambda c: jnp.tensordot(wn, c.astype(jnp.float32),
                                    axes=1).astype(dtype), client))
    return merge(frozen, jax.tree.map(lambda a: a.astype(dtype), params))


def follow_rounds(model, mcfg: dict, fl: dict, fleet: Dict[str, np.ndarray],
                  cx, cy, params, key, selected: np.ndarray,
                  participating: np.ndarray, H: np.ndarray, K: int,
                  precision: str = "reference"):
    """The global model after len(selected) rounds from `params`, with
    each round's selection, participation and local-iteration counts
    given ((R, S) arrays, the program's own decisions), the training
    computed here: the same round keys, the same minibatches, local SGD
    and FedAvg of the trainable leaves in `precision`."""
    dtype, matmul = _dtype(precision), MATMUL
    p = jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), params)
    key = jnp.asarray(key)
    for r in range(selected.shape[0]):
        key, kr = jax.random.split(key)
        _, _, k_train = jax.random.split(kr, 3)
        p = train_and_aggregate(model, mcfg, fl, fleet, cx, cy, p,
                                selected[r], participating[r], H[r],
                                k_train, K, dtype, matmul)
    return p
