"""The comparison that decides `correct`.

The tap holds the carry at the first round of each chunk the traffic
names; the reference recomputes that round from the carry, and five
numbers are read against the program's own outputs in its dense
history and in the carries:

* `probe`: |program's fleet-mean probe loss − reference's| / reference's,
  the round's model as the program saw it (the `round.probe` layer);
* `update`: the change of the fleet-mean probe loss that the round's
  aggregated model brings, the program's (its next round's probe less
  this one's) against the reference's, relative to the mean size of
  that change over the devices in the reference (local SGD and FedAvg,
  `round.local_update` and `round.aggregation`). The mean over devices
  of |change| does not vanish in a round whose gains and losses cancel
  in the fleet mean;
* `selection`: devices selected by one and not the other (the
  `rewafl_select` kernel), leaving out a swap of devices whose
  reference utilities tie at the top-K cut to within SELECT_TIE;
* `local_steps`: devices, selected alike, whose local-iteration count H
  differs, leaving out those whose stopping value ε lies within
  EPS_TIE of its threshold, where rounding decides;
* `model`: the aggregated model itself (`round.local_update` and the
  `fedavg` kernel of `round.aggregation`). Between two tapped carries
  the reference trains the chunk's rounds from the first carry's model,
  with the selections, participation and local-iteration counts the
  program decided (teacher-forced from its history and carries), and
  the change of each trainable parameter leaf over the chunk is
  compared by its norm: |‖program's change‖ − ‖reference's change‖|
  over the larger of the reference's and the median trainable leaf's,
  worst leaf;
* `frozen`, where the configuration declares `trainable`: the frozen
  leaves whose fingerprint in a tapped carry differs from the initial
  model's, counted over every tapped chunk so far. The program must
  never move them; it is compared exactly, at the limit its limits file
  gives or else 0.

Each number is the worst over the compared rounds. A cell compares the
numbers that have a limit in `limits/<workload>.json` (and `frozen`);
the others are read and printed only.
"""
from __future__ import annotations

from types import SimpleNamespace
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

# relative width of a tie: a few hundred float32 ulps, far below the
# gaps that separate devices but above the ulps by which a kernel's
# division may differ from XLA's
SELECT_TIE = 1e-5
# relative width around ε's threshold inside which the program's probe
# (same stated precision, another accumulation order) may decide either
# way
EPS_TIE = 1e-3


def selection_diff(selected: np.ndarray, ref) -> int:
    diff = np.flatnonzero(selected != ref.selected)
    if diff.size == 0:
        return 0
    u = ref.utility
    k = int(ref.selected.sum())
    fin = np.sort(u[np.isfinite(u)])[::-1]
    if k == 0 or k >= fin.size:
        return int(diff.size)
    uk, uk1 = float(fin[k - 1]), float(fin[k])
    band = SELECT_TIE * abs(uk)
    tie = abs(uk - uk1) <= band
    explained = tie & (np.abs(u[diff].astype(np.float64) - uk) <= band)
    return int((~explained).sum())


def steps_diff(selected: np.ndarray, new_H: np.ndarray, ref,
               eps_th: float) -> int:
    alike = selected == ref.selected
    diff = np.flatnonzero(alike & (new_H != ref.new_H))
    explained = np.abs(ref.eps[diff] - eps_th) <= EPS_TIE * eps_th
    return int((~explained).sum())


def readings(out, ref, eps_th: float) -> Dict[str, float]:
    """The four numbers of one round. `out` holds what the program (or
    the control in its place) produced: probe_before, probe_after,
    selected, new_H."""
    d_ref = ref.probe_after - ref.probe_before
    d_out = out.probe_after - out.probe_before
    return {
        "probe": abs(out.probe_before - ref.probe_before)
        / abs(ref.probe_before),
        "update": abs(d_out - d_ref) / max(ref.change, 1e-30),
        "selection": float(selection_diff(out.selected, ref)),
        "local_steps": float(steps_diff(out.selected, out.new_H, ref,
                                        eps_th)),
    }


_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


@jax.jit
def fingerprint(tree) -> jax.Array:
    """(leaves, 2) uint32 on the device: the wrapping sum of each leaf's
    bits, and the same weighted by a hash of each element's position, so
    that a leaf whose values move or swap reads another."""
    def one(a):
        bits = jax.lax.bitcast_convert_type(
            a, _UINT[a.dtype.itemsize]).astype(jnp.uint32).ravel()
        w = (jnp.arange(bits.size, dtype=jnp.uint32)
             * jnp.uint32(2654435761) + jnp.uint32(1))
        return jnp.stack([jnp.sum(bits, dtype=jnp.uint32),
                          jnp.sum(bits * w, dtype=jnp.uint32)])
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.zeros((0, 2), jnp.uint32)
    return jnp.stack([one(a) for a in leaves])


def frozen_moved(fp0: np.ndarray, fps) -> list:
    """For each tapped carry's fingerprint in `fps` (in chunk order), the
    frozen leaves whose fingerprint differed from the initial model's
    `fp0` there or at an earlier one."""
    moved = np.zeros(fp0.shape[0], bool)
    out = []
    for fp in fps:
        moved |= (np.asarray(fp) != fp0).any(axis=1)
        out.append(int(moved.sum()))
    return out


def norm_gap(p0, p_out, p_ref, trainable=None) -> float:
    """Worst trainable leaf of |‖p_out − p0‖ − ‖p_ref − p0‖| over the
    larger of ‖p_ref − p0‖ and the median trainable leaf's."""
    p0, p_out, p_ref = (reference.split(p, trainable)[1]
                        for p in (p0, p_out, p_ref))

    def norms(p):
        return np.array([np.linalg.norm(np.asarray(a, np.float64)
                                        - np.asarray(b, np.float64))
                         for a, b in zip(jax.tree.leaves(p),
                                         jax.tree.leaves(p0))])
    d_out, d_ref = norms(p_out), norms(p_ref)
    floor = max(float(np.median(d_ref)), 1e-30)
    return float(np.max(np.abs(d_out - d_ref) / np.maximum(d_ref, floor)))


def chunk_decisions(hist, snap, snap_next):
    """(selected, participating, H) of each round between two carries, as
    the program decided them: (R, S) arrays. A selected device that could
    not pay for its round fails it and is dropped for good, so its one
    failure, the gap between its selections and participations over the
    chunk, falls on its last selection."""
    r0, r1 = snap.round, snap_next.round
    sel = np.asarray(hist["selected"][r0:r1], bool)
    H = np.asarray(hist["H"][r0:r1], np.int32)
    n_part = (snap_next.state["n_participations"]
              - snap.state["n_participations"])
    failed = sel.sum(axis=0) - n_part
    part = sel.copy()
    for i in np.flatnonzero(failed > 0):
        part[np.flatnonzero(sel[:, i])[-1], i] = False
    return sel, part, H


def model_reading(cell, ref_model, fleet_np, cx, cy, snap, snap_next, hist,
                  who: str = "program") -> float:
    """`model` over the chunk from `snap` to `snap_next`: the program's
    model at `snap_next` (or the control's, trained in bfloat16 with the
    same decisions) against the reference's."""
    tr = cell.traffic
    sel, part, H = chunk_decisions(hist, snap, snap_next)

    def follow(precision):
        return reference.follow_rounds(
            ref_model, cell.config, tr["fl"], fleet_np, cx, cy, snap.params,
            snap.key, sel, part, H, int(tr["select"]), precision)

    p_ref = follow("reference")
    p_out = snap_next.params if who == "program" else follow("control")
    return norm_gap(snap.params, p_out, p_ref, cell.config.get("trainable"))


def program_out(hist, r: int):
    return SimpleNamespace(
        probe_before=float(hist["global_loss"][r]),
        probe_after=float(hist["global_loss"][r + 1]),
        selected=np.asarray(hist["selected"][r], bool),
        new_H=np.asarray(hist["H"][r], np.int32))


def round_readings(cell, ref_model, fleet_np, cx, cy, snap, hist,
                   who: str = "program"):
    """Readings of one compared round: the program's outputs, or the
    control's (`precision_out="control"`), against the reference."""
    tr = cell.traffic
    ref = reference.run_round(ref_model, cell.config, tr["fl"], fleet_np,
                              cx, cy, snap.params, snap.state, snap.key,
                              int(tr["select"]), "reference")
    if who == "program":
        out = program_out(hist, snap.round)
    else:
        c = reference.run_round(ref_model, cell.config, tr["fl"], fleet_np,
                                cx, cy, snap.params, snap.state, snap.key,
                                int(tr["select"]), "control")
        out = SimpleNamespace(probe_before=c.probe_before,
                              probe_after=c.probe_after,
                              selected=c.selected, new_H=c.new_H)
    return readings(out, ref, float(tr["fl"]["eps_th"]))


def worst(per_round) -> Dict[str, float]:
    """The largest reading of each number over the compared rounds (NaN
    when any is not finite)."""
    out: Dict[str, float] = {}
    for r in per_round:
        for k, v in r.items():
            bad = not np.isfinite(v) or not np.isfinite(out.get(k, 0.0))
            out[k] = float("nan") if bad else max(out.get(k, 0.0), v)
    return out


def over(value: float, limit: float) -> bool:
    return not (np.isfinite(value) and value <= limit)


def check(cell, ref_model, fleet_np, cx, cy, snaps, hist, log=None,
          who: str = "program", fp0=None):
    """({number: {"value": worst reading, "limit": limit}}, rounds with a
    reading over its limit) for the program's outputs (or the
    control's, `who="control"`). `fp0`, the initial model's frozen
    fingerprint, adds `frozen` from the snapshots' fingerprints."""
    limits = dict(cell.limits)
    moved = []
    if who != "program":   # the control's frozen leaves are the reference's
        limits.pop("frozen", None)
    elif fp0 is not None:
        limits.setdefault("frozen", 0)
        moved = frozen_moved(fp0, [snaps[c].fingerprint
                                   for c in sorted(snaps)])
    per_round = []
    for i, c in enumerate(sorted(snaps)):
        r = round_readings(cell, ref_model, fleet_np, cx, cy, snaps[c],
                           hist, who)
        if c + 1 in snaps:
            r["model"] = model_reading(cell, ref_model, fleet_np, cx, cy,
                                       snaps[c], snaps[c + 1], hist, who)
        if moved:
            r["frozen"] = float(moved[i])
        per_round.append(r)
        if log is not None:
            print(f"compared_round={snaps[c].round} who={who} "
                  + " ".join(f"{k}={v!r}" for k, v in r.items()),
                  file=log, flush=True)
    w = worst(per_round)
    if log is not None:
        print(f"worst who={who} "
              + " ".join(f"{k}={v!r}" for k, v in w.items()), file=log,
              flush=True)
    bad = sum(any(over(r[k], v) for k, v in limits.items() if k in r)
              for r in per_round)
    return ({k: {"value": w[k], "limit": v} for k, v in limits.items()},
            bad)
