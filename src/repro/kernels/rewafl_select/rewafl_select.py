"""Pallas TPU kernel: fused REWAFL utility → rank-space ε-greedy top-K.

One sequential pass over S-tiles of `FleetState`/`EnvState` leaves
computes the Eqn (2) utility in-register, maintains running exploit
(by utility) and explore (by the ε-greedy uniform draw) candidate lists
in VMEM scratch, and resolves the final selection in the last grid step
— the (S,) utility / rank / mask arrays never round-trip through HBM.
The kernel emits only the (K,) selected device indices + live flags; the
FedAvg epilogue (`ops.select_aggregate`) then gathers K delta rows and
reduces with `kernels/fedavg`, turning the dense (S, P) masked reduction
into a (K, P) one.

Ranking semantics match `core.selection` exactly: stable descending
order, ties toward the lower device index. The running candidate lists
are kept in that order and always precede the current tile in the merge
buffer, so first-max extraction preserves the global tie rule.

Two entry points share the kernel body:
  select_topk_flat   grid=(1,): whole fleet in one VMEM tile (7·4·S
                     bytes — fine to S≈100k).
  select_topk_tiled  grid=(S/block,): the S≥100k variant; VMEM holds one
                     (1, BLOCK_S) tile per leaf + the O(K) scratch.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30       # masking value for unavailable / padded devices
LIVE_THR = -1e29  # candidate values above this came from a real device
BLOCK_S = 2048    # devices per grid step in the tiled variant


def _pow_s(base: jax.Array, p: float) -> jax.Array:
    """Static-exponent `utility._pow`: exact at p == 1."""
    return base if p == 1 else base ** p


def _tile_utility(stat, t, e, residual, e0, avail, *, T_round: float,
                  alpha: float, beta: float) -> jax.Array:
    """Eqn (2) on one tile, mirroring `utility.rewafl_utility` op-for-op;
    unavailable devices are masked to NEG."""
    lat = jnp.where(t > T_round,
                    _pow_s(T_round / jnp.maximum(t, 1e-9), alpha), 1.0)
    head = residual - e0
    eng = jnp.where(e < head,
                    _pow_s(jnp.maximum(head / jnp.maximum(e, 1e-9),
                                       1e-9), beta), 0.0)
    return jnp.where(avail, stat * lat * eng, NEG)


def _first_max(buf_vals: jax.Array, buf_idx: jax.Array, iota: jax.Array
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(value, global index, buf with that slot killed) of the first
    maximum — reductions only, no lane-dim dynamic indexing (Mosaic)."""
    v = jnp.max(buf_vals)
    hit = buf_vals == v
    j = jnp.min(jnp.where(hit, iota, iota.shape[-1]))
    g = jnp.sum(jnp.where(iota == j, buf_idx, 0))
    return v, g, jnp.where(iota == j, NEG, buf_vals)


def _merge_candidates(cand_v, cand_i, tile_v, tile_i, c: int):
    """Top-c of [running candidates ++ tile], stable desc order. The
    running list precedes the tile (its global indices are smaller), so
    first-max extraction reproduces lax.top_k's tie rule."""
    buf_v = jnp.concatenate([cand_v, tile_v], axis=-1)
    buf_i = jnp.concatenate([cand_i, tile_i], axis=-1)
    iota = jax.lax.broadcasted_iota(jnp.int32, buf_v.shape, 1)
    vs, gs = [], []
    for _ in range(c):
        v, g, buf_v = _first_max(buf_v, buf_i, iota)
        vs.append(v)
        gs.append(g)
    return (jnp.stack(vs)[None, :].astype(jnp.float32),
            jnp.stack(gs)[None, :].astype(jnp.int32))


def _kernel(stat_ref, t_ref, e_ref, res_ref, e0_ref, avail_ref, rnd_ref,
            oidx_ref, olive_ref, xv, xi, rv, ri, *, T_round: float,
            alpha: float, beta: float, k_exploit: int, k_explore: int,
            n_tiles: int):
    i = pl.program_id(0)
    k = k_exploit + k_explore

    @pl.when(i == 0)
    def _init():
        xv[...] = jnp.full(xv.shape, NEG, jnp.float32)
        xi[...] = jnp.zeros(xi.shape, jnp.int32)
        rv[...] = jnp.full(rv.shape, NEG, jnp.float32)
        ri[...] = jnp.zeros(ri.shape, jnp.int32)

    avail = avail_ref[...] > 0.0
    util = _tile_utility(stat_ref[...], t_ref[...], e_ref[...],
                         res_ref[...], e0_ref[...], avail,
                         T_round=T_round, alpha=alpha, beta=beta)
    rnd = jnp.where(avail, rnd_ref[...], NEG)
    gidx = (i * util.shape[-1]
            + jax.lax.broadcasted_iota(jnp.int32, util.shape, 1))

    if k_exploit > 0:
        nv, ni = _merge_candidates(xv[...], xi[...], util, gidx,
                                   k_exploit)
        xv[...], xi[...] = nv, ni
    if k_explore > 0:
        # keep k explore candidates: after excluding the ≤ k_exploit
        # exploit picks, ≥ k_explore survive
        nv, ni = _merge_candidates(rv[...], ri[...], rnd, gidx, k)
        rv[...], ri[...] = nv, ni

    @pl.when(i == n_tiles - 1)
    def _resolve():
        # halves with no slots are left out at trace time (Mosaic has no
        # zero-width vectors) and live flags stay int32 (it cannot
        # truncate a materialised bool vector)
        halves_i, halves_l = [], []
        if k_exploit > 0:
            xii = xi[...]
            x_live = (xv[...] > LIVE_THR).astype(jnp.int32)
            halves_i.append(xii)
            halves_l.append(x_live)
        if k_explore > 0:
            rvv, rii = rv[...], ri[...]
            iota_c = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
            iota_r = jax.lax.broadcasted_iota(jnp.int32, (1, k_explore), 1)
            r_idx = jnp.zeros((1, k_explore), jnp.int32)
            r_live = jnp.zeros((1, k_explore), jnp.int32)
            cnt = jnp.int32(0)
            for m in range(k):
                at = iota_c == m
                g = jnp.sum(jnp.where(at, rii, 0))
                live = jnp.max(jnp.where(at, rvv, NEG)) > LIVE_THR
                pick = live & (cnt < k_explore)
                if k_exploit > 0:
                    taken = jnp.max(jnp.where(xii == g, x_live, 0)) > 0
                    pick = pick & ~taken
                slot = iota_r == jnp.where(pick, cnt, -1)
                r_idx = jnp.where(slot, g, r_idx)
                r_live = jnp.where(slot, 1, r_live)
                cnt = cnt + pick.astype(jnp.int32)
            halves_i.append(r_idx)
            halves_l.append(r_live)
        oidx_ref[...] = jnp.concatenate(halves_i, axis=-1)[0]
        olive_ref[...] = jnp.concatenate(halves_l, axis=-1)[0]


@functools.partial(jax.jit, static_argnames=(
    "k_exploit", "k_explore", "T_round", "alpha", "beta", "block_s",
    "interpret"))
def select_topk(stat, t, e, residual, e0, avail, rnd, *, k_exploit: int,
                k_explore: int, T_round: float, alpha: float,
                beta: float, block_s: int, interpret: bool = False):
    """Run the fused selection kernel over padded (S,) leaves (S a
    multiple of block_s; pad with avail=0). Returns ((K,) selected
    device indices, (K,) live flags as int32) with K = k_exploit +
    k_explore, exploit slots first, both halves in rank order."""
    from jax.experimental.pallas import tpu as pltpu

    S = stat.shape[-1]
    assert S % block_s == 0, (S, block_s)
    n_tiles = S // block_s
    k = k_exploit + k_explore
    kern = functools.partial(_kernel, T_round=T_round, alpha=alpha,
                             beta=beta, k_exploit=k_exploit,
                             k_explore=k_explore, n_tiles=n_tiles)
    vec = pl.BlockSpec((1, block_s), lambda i: (0, i))
    out = pl.BlockSpec((k,), lambda i: (0,))
    cx, cr = max(k_exploit, 1), max(k, 1)
    args = [a.reshape(1, S) for a in (
        stat.astype(jnp.float32), t.astype(jnp.float32),
        e.astype(jnp.float32), residual.astype(jnp.float32),
        e0.astype(jnp.float32), avail.astype(jnp.float32),
        rnd.astype(jnp.float32))]
    return pl.pallas_call(
        kern,
        grid=(n_tiles,),
        in_specs=[vec] * 7,
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((k,), jnp.int32)] * 2,
        scratch_shapes=[
            pltpu.VMEM((1, cx), jnp.float32),
            pltpu.VMEM((1, cx), jnp.int32),
            pltpu.VMEM((1, cr), jnp.float32),
            pltpu.VMEM((1, cr), jnp.int32),
        ],
        interpret=interpret,
    )(*args)


def select_topk_flat(stat, t, e, residual, e0, avail, rnd, **kw):
    """Single-tile variant: the whole fleet is one VMEM block."""
    return select_topk(stat, t, e, residual, e0, avail, rnd,
                       block_s=stat.shape[-1], **kw)


def select_topk_tiled(stat, t, e, residual, e0, avail, rnd, *,
                      block_s: int = BLOCK_S, **kw):
    """S≥100k variant: sequential grid over block_s-device tiles with
    the candidate lists carried in VMEM scratch."""
    return select_topk(stat, t, e, residual, e0, avail, rnd,
                       block_s=block_s, **kw)
