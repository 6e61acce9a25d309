"""Smoke run of the REWAFL campaign engine on a TPU.

Drives `run_fl` through the scan engine at the paper's CNN width
(`cnn@mnist`, small=False, ~207k parameters) over a 5,000-phone fleet
for 16 rounds on the kernel lowering (`kernel_backend="auto"`, which is
the two Pallas kernels on a TPU), runs the same campaign on the XLA
reference lowering, and checks the kernels against the reference. All
data and weights are made from `--seed`.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # fleet sharded over four chips

Two whole campaigns cannot be held to a tight tolerance against each
other: the FedAvg kernel and XLA sum the K client updates in a
different order, and local SGD grows that last-bit difference round
over round (on a v5e, to about 1e-3 relative in `global_loss` by round
8, at the default and at f32 matmul precision alike). So the campaigns
must select identically (`sel_count`), and the tight check is made
round by round: every round runs on both lowerings from the same carry
(the kernel lowering's), the selections must be identical, and the
fleet-mean probe loss of the two aggregated models must agree to
`RTOL`, as must every parameter leaf (relative to the leaf's largest
magnitude).

`--four-chips` runs only the sharded path and what it is compared with:
S=10,000 sharded four ways, a fleet whose round does not fit one chip,
then S=5,000 sharded four ways against S=5,000 on one chip. Sharding
does not change the order of any sum that feeds the next round, so
those two campaigns must agree as a whole.

It runs in one process, exits non-zero without printing a result when
JAX finds no TPU, and prints one JSON object as its last line:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core import METHODS, FLConfig, init_fleet_state  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.engine import make_chunk_fn  # noqa: E402
from repro.launch.fl_run import FLEET_KWARGS, build_task, run_fl  # noqa: E402
from repro.models.fl_models import make_fl_model  # noqa: E402
from repro.sim.devices import build_fleet  # noqa: E402
from repro.sim.dynamics import get_scenario, init_env_state  # noqa: E402

TASK, METHOD, LAM = "cnn@mnist", "rewafl", 0.8
K, ROUNDS, CHUNK, PER_CLIENT = 20, 16, 8, 64
RTOL = 1e-4


def say(**kw) -> None:
    print(" ".join(f"{k}={v}" for k, v in kw.items()), flush=True)


def peak_bytes(devices) -> list:
    """Peak device bytes of this process so far, one entry per device."""
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def campaign(S: int, backend: str, seed: int, shards=None):
    """One paper-width campaign through the public entry point."""
    import jax
    t0 = time.time()
    res = run_fl(task=TASK, method=METHOD, small=False, n_clients=S,
                 n_select=K, rounds=ROUNDS, chunk_size=CHUNK,
                 eval_every=CHUNK, target_acc=1.01, lam=LAM,
                 per_client=PER_CLIENT, seed=seed, kernel_backend=backend,
                 fleet_shards=shards)
    walls = np.asarray(res.chunk_wall_s)
    say(phase=f"S{S}_{backend}_shards{shards or 1}", S=S, K=K,
        rounds=res.rounds_run, compile_s=f"{res.compile_s:.3f}",
        steady_s_per_chunk=f"{walls[1:].mean():.4f}",
        rounds_per_chunk=CHUNK, wall_s=f"{time.time() - t0:.2f}",
        final_acc=f"{res.acc_curve[-1]:.4f}",
        process_peak_bytes=peak_bytes(jax.devices()[:shards or 1]))
    if res.rounds_run != ROUNDS:
        raise SystemExit(f"{res.method}/{backend}: ran {res.rounds_run} of "
                         f"{ROUNDS} rounds")
    for k, v in res.history.items():
        if not np.isfinite(np.asarray(v, np.float64)).all():
            raise SystemExit(f"{backend}: non-finite history[{k!r}]")
    return res


def max_rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.abs(b)))


def same_selection(a, b, what: str) -> None:
    if not np.array_equal(a.history["sel_count"], b.history["sel_count"]):
        raise SystemExit(f"{what}: sel_count differs")


def round_by_round(S: int, seed: int) -> None:
    """Each of ROUNDS rounds on both lowerings from the kernel lowering's
    carry: identical selections, and the aggregated models agree to RTOL
    in fleet-mean probe loss and in every parameter leaf. Also checks
    that the compiled kernel round holds the Pallas kernels."""
    import jax
    import jax.numpy as jnp
    model = make_fl_model(TASK, small=False)
    cfg = FLConfig(n_select=K)
    fleet = build_fleet(S, seed=seed, **FLEET_KWARGS)
    cx, cy, _ = build_task(TASK, S, LAM, per_client=PER_CLIENT, seed=seed)
    scen = get_scenario("static-paper")
    params = model.init(jax.random.PRNGKey(seed + 2))
    carry = (params, init_fleet_state(fleet, H0=cfg.policy.H0),
             init_env_state(fleet, scen))
    key = jax.random.PRNGKey(seed + 1)
    # the probe the round body takes, in blocks of 250 clients
    blocks = S // 250
    bx = cx[:, :cfg.probe_size].reshape((blocks, -1) + cx.shape[2:])
    by = cy[:, :cfg.probe_size].reshape(blocks, -1)

    @jax.jit
    def probe_loss(p, bx, by):
        return jnp.mean(jax.lax.map(
            lambda b: model.loss(p, {"x": b[0], "y": b[1]}), (bx, by)))

    def compiled(backend):
        chunk = make_chunk_fn(
            model, dataclasses.replace(cfg, kernel_backend=backend),
            METHODS[METHOD], chunk_size=1, scenario=scen)
        return chunk.lower(*carry, fleet, cx, cy, key,
                           jnp.asarray(0, jnp.int32)).compile()

    t0 = time.time()
    kern, ref = compiled("auto"), compiled("xla")
    n_calls = kern.as_text().count("tpu_custom_call")
    ma = kern.memory_analysis()
    say(phase="round_by_round", S=S, compile_s=f"{time.time() - t0:.3f}",
        tpu_custom_call_in_kernel_round=n_calls,
        kernel_round_temp_bytes=ma.temp_size_in_bytes,
        kernel_round_argument_bytes=ma.argument_size_in_bytes)
    if n_calls == 0:
        raise SystemExit("the compiled kernel round holds no Pallas kernel")

    worst_loss = worst_param = 0.0
    for r in range(ROUNDS):
        args = (*carry, fleet, cx, cy, key, jnp.asarray(r, jnp.int32))
        pk, sk, ek, key_next, hk = kern(*args)
        px, _, _, _, hx = ref(*args)
        if not np.array_equal(hk["selected"], hx["selected"]):
            raise SystemExit(f"round {r}: the lowerings select differently")
        lk, lx = (float(probe_loss(p, bx, by)) for p in (pk, px))
        if not (np.isfinite(lk) and abs(lk - lx) <= RTOL * abs(lx)):
            raise SystemExit(f"round {r}: probe loss of the aggregated "
                             f"model {lk} (kernel) vs {lx} (xla)")
        for a, b in zip(jax.tree.leaves(pk), jax.tree.leaves(px)):
            diff = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
            scale = float(np.max(np.abs(np.asarray(b))))
            if not diff <= RTOL * scale:
                raise SystemExit(f"round {r}: a parameter leaf {b.shape} "
                                 f"differs by {diff} (largest |x| {scale})")
            worst_param = max(worst_param, diff / scale if scale else 0.0)
        worst_loss = max(worst_loss, abs(lk - lx) / abs(lx))
        carry, key = (pk, sk, ek), key_next
    say(parity="kernel_vs_xla_round_by_round", rounds=ROUNDS,
        selections="identical", max_rel_probe_loss=f"{worst_loss:.3e}",
        max_rel_param=f"{worst_param:.3e}")


def one_chip(seed: int) -> None:
    import jax
    S = 5_000
    leaves = jax.tree.leaves(
        make_fl_model(TASK, small=False).init(jax.random.PRNGKey(seed + 2)))
    say(model=TASK, n_params=sum(x.size for x in leaves),
        widths=[tuple(x.shape) for x in leaves])
    kern = campaign(S, "auto", seed)
    ref = campaign(S, "xla", seed)
    same_selection(kern, ref, "kernel_vs_xla_campaign")
    drift = max_rel(kern.history["global_loss"], ref.history["global_loss"])
    say(parity="kernel_vs_xla_campaign", sel_count="identical",
        max_rel_global_loss=f"{drift:.3e}")
    round_by_round(S, seed)


def four_chips(seed: int) -> None:
    # the largest fleet first, so the process-wide peaks it prints are
    # its own
    campaign(10_000, "auto", seed, shards=4)
    four = campaign(5_000, "auto", seed, shards=4)
    one = campaign(5_000, "auto", seed)
    same_selection(four, one, "four_shards_vs_one_chip")
    ga, gb = four.history["global_loss"], one.history["global_loss"]
    if not np.allclose(ga, gb, rtol=RTOL, atol=0.0):
        raise SystemExit(f"four_shards_vs_one_chip: global_loss differs "
                         f"beyond rtol={RTOL}: {ga.tolist()} vs "
                         f"{gb.tolist()}")
    say(parity="four_shards_vs_one_chip_S5000", sel_count="identical",
        max_rel_global_loss=f"{max_rel(ga, gb):.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet-sharded four-chip path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform}")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        raise SystemExit(f"needs {need} chips, JAX found {len(devices)}")
    say(cache_dir=compile_cache.configure(), jax=jax.__version__,
        devices=len(devices), kind=repr(devices[0].device_kind))

    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
